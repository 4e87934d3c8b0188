"""Needed FLOPs per token x steady tokens/s/chip over the chip's bf16
peak: an end-to-end utilization (recomputed work is not counted), not a
kernel's share."""

from benchmark.reduce import peaks


def read(run):
    rate = run["end_to_end"].get("train_tokens_per_s_per_chip")
    if rate is None or run["device"]["platform"] != "tpu":
        return None
    seq = run["cell"].traffic["seq"]
    flops, _ = peaks.peak(run["device"]["kind"])
    needed = run["cell"].family.needed
    return 100.0 * needed.train_flops_per_token(run["config"], seq) * rate / flops
