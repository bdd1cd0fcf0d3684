"""Modality-frontend STUBS (per the assignment spec).

``[audio]`` / ``[vlm]`` architecture entries specify the transformer backbone
only; the modality frontend supplies *precomputed* frame/patch embeddings via
``input_specs()``. These constants define the stub geometry the launchers and
dry-run share.

* vision (InternVL2): 256 image tokens per sample, 1024-dim patch embeddings
  (the pixel-shuffled InternViT output dimensionality class).
* audio (HuBERT): 50 frames/s conv-extractor output, 512-dim (the wav2vec2
  conv stack's channel width); for shape cells the frame count equals the
  assigned seq_len (the backbone sees one embedding per frame).
"""

from __future__ import annotations

VISION_STUB_DIM = 1024
VISION_TOKENS = 256
AUDIO_STUB_DIM = 512
