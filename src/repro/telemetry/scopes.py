"""Names of the engines' layers in a device profile.

Each name is a ``jax.named_scope`` that an engine opens at its own call
site of a layer, or the ``name=`` of a Pallas kernel. Named scopes change
only the metadata of the compiled program (each op's ``op_name``, which a
profiler shows as ``tf_op``): the ops, their fusion and their results are
the same with and without them. A profile of any caller of the engines
can therefore attribute each device op to the innermost name below.
"""

#: The per-run arrival and service draws: the configuration's ``build_inputs``.
MC_DRAWS = "mc_draws"
#: The slot loop of ``simulate`` and of the controller's epoch: its body and
#: the stacking of its outputs.
GMSA_SCAN = "gmsa_scan"
#: One dispatch decision, whichever policy makes it.
GMSA_DECIDE = "gmsa_decide"
#: The controller's epoch loop: epoch bookkeeping and the WAN and sync bills.
PLACED_EPOCHS = "placed_epochs"
#: The slow-timescale placement rule.
PLACED_RULE = "placed_rule"
#: The off-schedule recovery epoch on a site's death edge.
PLACED_RECOVERY = "placed_recovery"
#: The Pallas kernel of the fused GMSA scores and argmin.
GMSA_SCORE = "gmsa_score"
#: The Pallas kernel of the chunked SSD scan.
SSD_SCAN = "ssd_scan"
