"""Host clock across one ``DecodeEngine.decode()`` call, the token
read-back included: the mean over the window of the benchmark's span
around the call (``decode_step_ms.mixed``'s rule)."""

from benchmarks.harness.readers import mean_ms


def read(run):
    return mean_ms(run, "decode_step_s")
