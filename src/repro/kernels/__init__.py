"""Pallas TPU kernels for SCBF's per-loop gradient pass.

The compute hot-spot the paper optimises (via pruning) is the per-loop
channel-norm + selection pass over every gradient matrix — a
bandwidth-bound reduction + masked rewrite.  Three fused kernels:

  channel_norm  — one pass over G producing row (input-channel) and
                  column (output-channel) squared norms
  select_mask   — threshold-masked gradient rewrite (the "Process
                  Gradients" step) fused with the pairwise score test
  apoz          — zero-fraction accumulation over activation tiles for
                  the APoZ pruning statistic

``ops.py`` exposes jit'd wrappers (interpreted off the TPU);
``ref.py`` holds the pure-jnp oracles the tests sweep against.
"""
from repro.kernels.ops import (channel_norms, select_mask, apoz_counts,
                               scbf_select_fused)
