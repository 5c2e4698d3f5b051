"""The token step's latent attention kernel (``latent_decode_attention``,
``ops/pallas/decode_attention.py``: one call a latent layer a decode
step) against its roofline: the least time of a call — the rows the busy
slots' queries see read once at the 1,152 bytes a row needs
(``serve_cache_rows_read_total{kind="latent"}`` a step;
``families/bailing_hybrid.py: latent_decode_bytes``), or its FLOPs —
over the mean device time of the kernel's events in the traced tail.
The kernel fetches whole blocks of 512 rows of 1,280 bytes, so what it
moves is more than the least."""

from benchmarks.harness.latent_counts import (
    decode_step_counts, kernel_roofline_pct)


def read(run):
    got = decode_step_counts(run)
    if got is None:
        return None
    return kernel_roofline_pct(
        run, "latent_decode_attention",
        run.family.latent_decode_flops(run.config, got["rows"]),
        run.family.latent_decode_bytes(run.config, got["rows"]))
