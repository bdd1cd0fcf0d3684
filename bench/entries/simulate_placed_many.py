"""Entry ``simulate_placed_many``: n_runs runs under the two-timescale
controller, with the traffic's placement rule, epochs and site loss.

Reference semantics: per epoch of W slots the slow rule moves each
dataset's layout halfway (the move budget) toward a softmin over the
epoch-mean omega*PUE (dead sites excluded), the WAN bills the move and the
replicas' sync, and the ratios are rebuilt for the new layout. On the slot a
site dies, its backlog re-enters as arrivals, its data is re-replicated over
the survivors and the rule re-places at once (the recovery epoch, billed on
that slot); from then on every slot's cost row follows the carried ratios.
"""

import jax
import jax.numpy as jnp
import numpy as np

import plugins
import reference
from cell import scoped

ENGINE = "repro.placement.controller"
ENTRY_FN = "simulate_placed_many"
N_RUNS_ARG = 6

F32 = np.float32
_EPS = reference.EPS
_DEAD_PENALTY = F32(1e6)
_REPLICA_THRESHOLD = F32(0.01)


# ---------------------------------------------------------------------------
# the program
# ---------------------------------------------------------------------------

def program(cfg, traffic, build_inputs, policy, n_runs, mesh):
    """run(key) -> the controller's outputs of one evaluation."""
    from repro.placement import PlacementConfig, make_adaptive_rule
    from repro.placement.controller import simulate_placed_many
    from repro.traces.bandwidth import bandwidth_draw
    from repro.traces.faults import scheduled_failure_trace

    f = cfg["fields"]
    v = float(f["v"])
    lo, hi = cfg["bandwidth_gbps"]
    k_bw = jax.random.split(jax.random.key(f["trace_seed"]), 6)[2]
    up, down = bandwidth_draw(k_bw, f["n_sites"], lo, hi)
    p = traffic["placement"]
    if p["rule"] != "adaptive":
        raise ValueError(f"unknown placement rule {p['rule']!r}")
    rule = scoped(make_adaptive_rule(up, temp=p["temp"], project_iters=p["project_iters"]),
                  "bench_placement_rule")
    pcfg = PlacementConfig(epoch_slots=p["epoch_slots"], move_budget=p["move_budget"],
                           dataset_gb=p["dataset_gb"], energy_per_gb=p["energy_per_gb"],
                           update_fraction=p["update_fraction"],
                           manager_share=f["manager_share"], map_share=f["map_share"])
    loss = traffic["site_loss"]
    alive = jnp.asarray(scheduled_failure_trace(
        f["t_slots"], f["n_sites"], [(loss["site"], loss["at_slot"], None)]))

    def run(key):
        return simulate_placed_many(build_inputs, up, down, policy, rule, key, n_runs,
                                    pcfg, v, alive=alive, mesh=mesh)

    return run


def digest(outs, rows) -> dict:
    """As ``simulate_many``'s, plus each slot's recovery bill and each
    run's WAN and sync bills of the day."""
    out = plugins.load("entries", "simulate_many").digest(outs, rows)
    out.update(
        slot_recovery=outs.recovery_cost[rows],
        wan_cost=jnp.sum(outs.wan_cost[rows], axis=-1),
        sync_cost=jnp.sum(outs.sync_cost[rows], axis=-1),
    )
    return out


def shapes(n_runs, n_rows, t_slots, k_types) -> dict:
    out = plugins.load("entries", "simulate_many").shapes(
        n_runs, n_rows, t_slots, k_types)
    out.update(slot_recovery=(n_rows, t_slots), wan_cost=(n_rows,), sync_cost=(n_rows,))
    return out


# ---------------------------------------------------------------------------
# the reference
# ---------------------------------------------------------------------------

def _rule(wpue_bar, alive, sizes, temp, iters):
    """(K, N) target layout: softmin over sites, dead sites at no weight and
    no storage, projected onto the storage caps (none but the dead's 0)."""
    k = sizes.shape[0]
    scores = np.broadcast_to(wpue_bar[None, :], (k, wpue_bar.shape[0])).astype(F32)
    scores = scores + _DEAD_PENALTY * (F32(1) - alive)[None, :]
    z = -scores / F32(max(temp, 1e-6))
    z = np.exp(z - z.max(axis=1, keepdims=True))
    target = (z / z.sum(axis=1, keepdims=True, dtype=F32)).astype(F32)
    finite = alive < 0.5                         # cap 0 where dead, inf elsewhere
    cap = np.where(finite, F32(0), F32(np.inf))
    p = target.copy()
    for _ in range(iters):
        load = (p * sizes[:, None]).sum(axis=0, dtype=F32)
        with np.errstate(divide="ignore", invalid="ignore"):
            scale = np.where(finite, np.minimum(F32(1), cap / np.maximum(load, _EPS)), F32(1))
        p = (p * scale[None, :]).astype(F32)
        head = np.where(finite, np.maximum(cap - (p * sizes[:, None]).sum(axis=0, dtype=F32),
                                           F32(0)), F32(1e9))
        w = target * head[None, :] + _EPS
        deficit = np.maximum(F32(1) - p.sum(axis=1, dtype=F32), F32(0))
        p = (p + deficit[:, None] * w / w.sum(axis=1, keepdims=True, dtype=F32)).astype(F32)
    return (p / np.maximum(p.sum(axis=1, keepdims=True, dtype=F32), _EPS)).astype(F32)


def _move_bill(d_old, d_new, sizes, epg, omega, pue):
    """(cost, energy, GB) of morphing d_old into d_new over (..., K, N):
    exporters ship to importers in proportion to their deficits, each byte
    priced at the mean of its two endpoints' weights."""
    delta = d_new - d_old
    out = np.maximum(-delta, F32(0)) * sizes[..., None]
    inn = np.maximum(delta, F32(0)) * sizes[..., None]
    share = inn / np.maximum(inn.sum(axis=-1, keepdims=True, dtype=F32), _EPS)
    o_tot, s_tot = out.sum(axis=-1, dtype=F32), share.sum(axis=-1, dtype=F32)

    def bill(w):
        ow = (out * w[..., None, :]).sum(axis=-1, dtype=F32)
        sw = (share * w[..., None, :]).sum(axis=-1, dtype=F32)
        return F32(0.5) * ((ow * s_tot).sum(axis=-1, dtype=F32)
                           + (o_tot * sw).sum(axis=-1, dtype=F32))

    return epg * bill(omega * pue), epg * bill(pue), (o_tot * s_tot).sum(axis=-1, dtype=F32)


def _evac_bill(d_masked, d_drop, sizes, epg, omega, pue):
    """(cost, GB) of re-replicating the lost shares from the survivors that
    hold copies (each destination sourced from every other holder)."""
    need = np.maximum(d_drop - d_masked, F32(0)) * sizes[..., None]
    lost = d_masked.sum(axis=-1, keepdims=True, dtype=F32) <= 1e-9
    src = np.where(lost, d_drop, d_masked)
    z_raw = np.maximum(src.sum(axis=-1, keepdims=True, dtype=F32) - src, F32(0))
    z = np.maximum(z_raw, _EPS)
    col = z_raw / z

    def half(w):
        sw = (src * w).sum(axis=-1, keepdims=True, dtype=F32)
        mean = np.maximum(sw - src * w, F32(0)) / z
        return F32(0.5) * (need * (mean + w * col)).sum(axis=(-2, -1), dtype=F32)

    return epg * half(omega * pue), (need * col).sum(axis=(-2, -1), dtype=F32)


def _sync_bill(d, sizes, epg, wpue_bar, update_fraction):
    """Replication premium: each materialized replica beyond the first
    absorbs ``update_fraction`` of its dataset per epoch at the mean price."""
    live = np.where(d >= _REPLICA_THRESHOLD, d, F32(0))
    tot = live.sum(axis=-1, keepdims=True, dtype=F32)
    live = np.where(tot > _EPS, live / np.maximum(tot, _EPS), d)
    eff = F32(1) / np.maximum((live * live).sum(axis=-1, dtype=F32), _EPS)
    prem = F32(update_fraction) * np.maximum(eff - F32(1), F32(0))
    return (prem * sizes).sum(axis=-1, dtype=F32) * epg * wpue_bar.mean(dtype=F32)


def alive_mask(t_slots, n_sites, site, at_slot) -> np.ndarray:
    alive = np.ones((t_slots, n_sites), F32)
    alive[at_slot:, site] = 0
    return alive


def reference_digest(cfg, traffic, scen, arr, mu, precision="highest", forced=None):
    """The controller over R runs at once: per-slot digests, plus each
    run's WAN, sync and recovery bills. ``forced`` replays given decisions,
    as in ``reference.simulate``."""
    f, pl = cfg["fields"], traffic["placement"]
    n_runs, t_slots, k = arr.shape
    n = mu.shape[2]
    w_slots = min(pl["epoch_slots"], t_slots)
    v, mb, epg = F32(f["v"]), F32(pl["move_budget"]), F32(pl["energy_per_gb"])
    temp, iters, upd = pl["temp"], pl["project_iters"], pl["update_fraction"]
    sizes = np.full((k,), pl["dataset_gb"], F32)
    up, down = scen["up"], scen["down"]
    loss = traffic["site_loss"]
    alive = alive_mask(t_slots, n, loss["site"], loss["at_slot"])
    alive_prev = np.concatenate([np.ones((1, n), F32), alive[:-1]])
    omega, pue, p_it = scen["omega"], scen["pue"], scen["p_it"]

    def rebuild(d):
        return reference.allocation(d, up, down, f["manager_share"], f["map_share"])

    def tables(r, w):                                  # r (R,K,N,N), w (W,N)
        if precision == "highest":
            out = np.einsum("rkij,tj->rtki", r.astype(np.float64), w.astype(np.float64))
        else:
            rh, rl = reference.split_bf16(r)
            wh, wl = reference.split_bf16(w)
            out = sum(np.einsum("rkij,tj->rtki", a, b) for a, b in ((rh, wh), (rh, wl), (rl, wh)))
        return (out.astype(F32) * p_it[None, None, :, None]).astype(F32)

    q = np.zeros((n_runs, n, k), F32)
    d = np.broadcast_to(scen["data_dist"], (n_runs, k, n)).astype(F32).copy()
    r0 = np.broadcast_to(scen["r"], (n_runs, k, n, n)).astype(F32)
    rows = {"slot_cost": [], "slot_energy": [], "gap": [], "slot_recovery": []}
    f_all, q_tot = [], []
    wan_c = np.zeros((n_runs,), np.float64)
    sync_c = np.zeros((n_runs,), np.float64)
    for e in range(t_slots // w_slots):
        sl = slice(e * w_slots, (e + 1) * w_slots)
        a_b = alive_prev[sl][0]
        dead_b = bool(np.any(a_b < 0.5))
        wpue_e = omega[sl] * pue[sl]
        wbar = wpue_e.mean(axis=0, dtype=F32)
        if e == 0:
            d_new = d
        else:
            target = _rule(wbar, a_b, sizes, temp, iters)[None]
            if dead_b:
                target = reference.renorm(target * a_b[None, None, :], d, axis=-1)
            stepped = d + mb * (target - d)
            d_new = (stepped / np.maximum(stepped.sum(axis=-1, keepdims=True, dtype=F32), _EPS)).astype(F32)
        c_w, _, _ = _move_bill(d, d_new, sizes[None], epg, omega[sl][0], pue[sl][0])
        wan_c += c_w
        sync_c += _sync_bill(d_new, sizes[None], epg, wbar, upd)
        r_e = r0 if e == 0 else rebuild(d_new)
        if dead_b:
            r_m = r_e * a_b[None, None, None, :]
            r_e = (r_m / np.maximum(r_m.sum(axis=-1, keepdims=True, dtype=F32), _EPS)).astype(F32)
        ec_all, er_all = tables(r_e, wpue_e), tables(r_e, pue[sl])    # (R, W, K, N)
        d_c, r_c, fired = d_new, r_e, False
        for j in range(w_slots):
            t = e * w_slots + j
            al, ap = alive[t], alive_prev[t]
            died = ap * (F32(1) - al)
            a = arr[:, t].copy()
            m = mu[:, t] * al[None, :, None]
            burst = (q * died[None, :, None]).sum(axis=1, dtype=F32)
            q = np.where(al[None, :, None] > 0.5, q, F32(0))
            d_masked = d_c * al[None, None, :]
            surv = d_masked.sum(axis=-1, keepdims=True, dtype=F32)
            uni = np.broadcast_to(al / max(al.sum(), F32(1)), d_masked.shape)
            d_drop = np.where(surv > 1e-9, d_masked / np.maximum(surv, F32(1e-9)), uni).astype(F32)
            a = (a + burst).astype(F32)
            rec = np.zeros((n_runs,), F32)
            if np.any(died > 0.5):
                tgt = reference.renorm(_rule(omega[t] * pue[t], al, sizes, temp, iters)[None]
                              * al[None, None, :], d_drop, axis=-1)
                d_rec = d_drop + mb * (tgt - d_drop)
                d_rec = (d_rec / np.maximum(d_rec.sum(axis=-1, keepdims=True, dtype=F32), _EPS)).astype(F32)
                ev_c, ev_g = _evac_bill(d_masked, d_drop, sizes[None], epg, omega[t], pue[t])
                mv_c, _, mv_g = _move_bill(d_drop, d_rec, sizes[None], epg, omega[t], pue[t])
                r_rec = rebuild(d_rec) * al[None, None, None, :]
                r_c = (r_rec / np.maximum(r_rec.sum(axis=-1, keepdims=True, dtype=F32), _EPS)).astype(F32)
                d_c = d_rec
                rec = (ev_c + mv_c).astype(F32)
                fired = True
            if fired:
                w_t = (omega[t] * pue[t])[None]
                ec, er = tables(r_c, w_t)[:, 0], tables(r_c, pue[t][None])[:, 0]
            else:
                ec, er = ec_all[:, j], er_all[:, j]
            score = a[:, :, None] * (np.swapaxes(q - m, 1, 2) + v * ec)
            fm, gap = reference.decide(score, ec, a, v, t, forced, alive=al)
            fa = fm * a[:, None, :]
            rows["slot_cost"].append((fa * np.swapaxes(ec, 1, 2)).sum(axis=(1, 2), dtype=F32))
            rows["slot_energy"].append((fa * np.swapaxes(er, 1, 2)).sum(axis=(1, 2), dtype=F32))
            rows["gap"].append(gap)
            rows["slot_recovery"].append(rec)
            q = np.maximum(q + fa - m, F32(0))
            f_all.append(fm)
            q_tot.append(q.sum(axis=(1, 2), dtype=F32))
        d = d_c
    out = reference.slot_digest(rows, f_all, q_tot)
    out["wan_cost"], out["sync_cost"] = wan_c, sync_c
    return out
