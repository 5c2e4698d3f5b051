"""The token step's latent attention kernel (``latent_decode_attention``,
``ops/pallas/decode_attention.py``: one call a layer a decode step, 64
heads here) against its roofline: the least time of a call — the rows
the busy slots' queries see read once at the 1,152 bytes a row needs
(``families/kimi_k2.py: latent_decode_bytes``), or its FLOPs (139 kFLOP a
row at 64 heads: 109 FLOP a byte against the chip's 240) — over the mean
device time of the kernel's events in the traced tail, found by the
kernel's name.  The kernel fetches whole blocks of 512 rows of 1,280
bytes, so what it moves is more than the least."""

from benchmarks.harness.latent_counts import kernel_roofline_pct
from benchmarks.harness.mla_counts import decode_step_counts


def read(run):
    got = decode_step_counts(run)
    if got is None:
        return None
    return kernel_roofline_pct(
        run, "latent_decode_attention",
        run.family.latent_decode_flops(run.config, got["rows"]),
        run.family.latent_decode_bytes(run.config, got["rows"]))
