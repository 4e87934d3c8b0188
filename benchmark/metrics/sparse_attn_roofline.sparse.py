"""Latent rows the decode steps traced had to read (``min(live,
index_topk)`` rows a live slot, a layer a step) over (the device time
under ``attn.select`` and ``attn.sparse`` in the decode block programs
x the chip's HBM peak). Bound: memory. The time holds the choice and
the attention over the chosen, whatever implements them: a masked dense
read or a slow ``top_k`` reads low, as it should."""

from benchmark.reduce import mla_dsa_moe, peaks


def read(run):
    steps = mla_dsa_moe.steps_traced(run)
    live = mla_dsa_moe.traced(run, "live_slots")
    tokens = mla_dsa_moe.traced(run, "kv_live_tokens")
    seconds = mla_dsa_moe.block_seconds(
        run, mla_dsa_moe.SELECT, mla_dsa_moe.SPARSE)
    if not steps or not seconds or live is None or tokens is None \
            or not mla_dsa_moe.on_the_chip(run):
        return None
    _, bw = peaks.peak(run["device"]["kind"])
    needed, config = run["cell"].family.needed, run["config"]
    need = (steps * config["num_hidden_layers"]
            * needed.selected_positions(config, tokens, live)
            * needed.selected_row_bytes(config))
    return 100.0 * need / (seconds * bw)
