"""Model FLOP/s utilization: tokens per second per chip times the
operations the forward and backward passes REQUIRE per token (matrix
multiplications of the blocks and the tied head, causal attention at
half the square, recomputation not counted: the family's count) over the
chip's bf16 peak."""


def read(run):
    rate = run.end_to_end.get("train_tokens_per_s_per_chip")
    if rate is None or run.peaks is None:
        return None
    need = run.family.train_flops_per_token(run.config,
                                            run.facts["seq_len"])
    return 100.0 * rate * need / run.peaks["bf16_flops_per_s"]
