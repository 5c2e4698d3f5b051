"""The decode step's share of its roofline for a model with
latent-attention and recurrent-state layers: the least time of a step —
every weight outside the routed experts once, each routed expert a pair
TOUCHED once, every slot's recurrent and convolution state read and
written once, the latent rows the queries read at 1,152 bytes a row, or
the FLOPs, whichever takes longer — over the device time of one run of
the decode program in the traced tail.  The counting functions are the
family's; each new kernel has a share of its own beside this
(``kda_step_roofline_pct.longform``,
``latent_decode_roofline_pct.longform``)."""

from benchmarks.harness.latent_counts import decode_roofline_pct as read  # noqa: F401
