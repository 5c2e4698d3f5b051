"""The decode step's share of its roofline for a model with state-space
and attention layers: the least time of a step — every weight outside
the routed experts once (the tied embedding once, as the head), each
routed expert a pair TOUCHED once, every slot's recurrent and
convolution state read and written once, the K/V rows the queries read
at 4,096 bytes a position, or the FLOPs, whichever takes longer — over
the device time of one run of the decode program in the traced tail: the
share of the whole step.  The counting functions are the family's.  The
recurrence's token step has no kernel of its own and so no share of its
own (XLA's lowering was faster than the kernel written for it: PERF.md
section 6, PR 41); it is the largest item of this one."""

from benchmarks.harness.ssm_counts import decode_roofline_pct as read  # noqa: F401
