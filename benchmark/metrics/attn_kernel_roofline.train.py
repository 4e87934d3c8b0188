"""Needed causal-attention FLOPs, forward and backward, of the steps
traced over (time in the Mosaic kernels x the chip's bf16 peak). Bound:
compute. The forward that remat runs a second time is in the time and
not in the FLOPs, as for any utilization here."""

from benchmark.reduce import peaks


def read(run):
    tr = run["trace"]
    if not tr or not tr["mosaic_s"] or run["device"]["platform"] != "tpu":
        return None
    seq = run["cell"].traffic["seq"]
    tokens = tr["modules_run"] * run["counters"]["tokens_per_step_per_chip"]
    flops, _ = peaks.peak(run["device"]["kind"])
    needed = run["cell"].family.needed
    need = needed.attention_train_flops_per_token(run["config"], seq) * tokens
    return 100.0 * need / (tr["mosaic_s"] * flops)
