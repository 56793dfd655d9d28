"""Model FLOP utilisation of the whole step, in %: model FLOPs per token
(``train_flops_per_token`` of ``bench/arch/<model_type>.py``: forward and
backward, no recomputation, causal attention) times the window's tokens per second, over the cell's chips
times one chip's bf16 peak (``bench/peaks.json``).  The bf16 peak is the
ceiling of a float32 matmul at default precision, which the TPU runs as one
bfloat16 pass."""


def read(run):
    if run.peak is None or run.tokens_per_s is None:
        return None
    return (run.tokens_per_s * run.flops_per_token
            / (run.chips * run.peak["bf16_flops_per_s"]) * 100.0)
