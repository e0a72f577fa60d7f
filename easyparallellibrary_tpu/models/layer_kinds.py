"""What a layer keeps per slot: the vocabulary a decoder's
``cfg.layer_kinds()`` answers in and ``serving/kv_cache.py`` allocates
from, in ONE module that both import (the cache imports no decoder).  A new
kind of layer state is one string here, its leaves in
``kv_cache.cache_leaves``, its rule beside the others there and its name
in ``kv_cache.step_lowerings``' order."""

# A K/V pair under the slot's cursor (GPT, the hybrid, LFM2, SmallThinker's
# full layers); a Mamba layer's convolution window and scan state
# (models/jamba.py); a gated short convolution's window, its whole state
# (models/lfm2_moe.py).
ATTENTION, MAMBA, CONV = "attention", "mamba", "conv"
# One latent leaf under the cursor (models/glm_moe.py); that leaf and an
# indexer's keys, a ring of latent rows behind a window
# (models/dots3_note.py).
LATENT, SPARSE_LATENT, WINDOW_LATENT = "latent", "sparse_latent", "window_latent"
# The K/V pair as a ring behind a window (models/smallthinker.py).
WINDOW_KV = "window_kv"
# A gated delta-rule linear attention's convolution window and its state, a
# float32 matrix a value head (models/gigachat.py).
GATED_DELTA = "gated_delta"

# ``layer_types`` of a published config whose layers differ in their latent
# attention (models/dots3_note.py): what ``cfg.latent_dims`` is asked by.
FULL, SLIDING = "full_attention", "sliding_attention"
