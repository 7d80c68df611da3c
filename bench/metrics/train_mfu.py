"""Model FLOP/s utilization of the traced training job: the operations
the forward and backward passes require per token (``bench/flops.py``),
times the window's tokens per second, over the chips' bf16 peak
(``bench/peaks.json``; a kind not in it is an error), in %.  Nothing to
read off a TPU: a CPU run gives no device metric."""

from bench import flops


def read(ctx):
    c, dev = ctx["counters"], ctx["device"]
    if (not dev or dev["platform"] != "tpu" or "tokens_per_s" not in c
            or "flops_per_token" not in c):
        return None
    peak = flops.peaks(dev["kind"])["bf16_flops_per_s"]
    return 100.0 * c["tokens_per_s"] * c["flops_per_token"] / (
        c["chips"] * peak)
