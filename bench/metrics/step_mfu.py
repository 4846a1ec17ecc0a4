"""Model FLOP/s of the window's steps over the chips' bf16 peak, in %.

Model FLOPs per token come from bench/flops.py (no recomputation), the
rate from the steps completed in the window over the window's length;
nothing where no step completed."""


def read(record):
    if not record["steps"]:
        return None
    rate = record["steps"] * record["tokens_per_step"] / record["window_s"]
    peak = record["chips"] * record["peak"]["bf16_flops_per_s"]
    return 100.0 * record["flops_per_token"] * rate / peak
