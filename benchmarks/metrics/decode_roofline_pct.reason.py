"""The decode step's share of its roofline for a model whose every layer
is latent attention: the least time of a step — every weight outside the
routed experts once, each routed expert a pair TOUCHED once, the latent
rows the busy slots' queries read in every layer at the 1,152 bytes a
row needs (the traced tail's own boundaries), or the FLOPs, whichever
takes longer — over the device time of one run of the decode program in
the traced tail: the share of the whole step.  The counting functions
are the family's (``families/kimi_k2.py``); the latent kernel has a share
of its own beside this (``latent_decode_roofline_pct.reason``)."""

from benchmarks.harness.mla_counts import decode_roofline_pct as read  # noqa: F401
