"""Index keys the decode steps traced had to read (the tokens resident
at the traced dispatches x the bytes of one index key, a layer a step)
over (the device time under ``attn.index`` in the decode block programs
x the chip's HBM peak). Bound: memory. The time is the scope's, whatever implements it
(the indexer's projections beside the scores); the bytes are the live
positions' keys alone, so a read of the padded cache reads low."""

from benchmark.reduce import mla_dsa_moe, peaks


def read(run):
    steps = mla_dsa_moe.steps_traced(run)
    seconds = mla_dsa_moe.block_seconds(run, mla_dsa_moe.INDEX)
    tokens = mla_dsa_moe.traced(run, "kv_live_tokens")
    if not steps or not seconds or tokens is None \
            or not mla_dsa_moe.on_the_chip(run):
        return None
    _, bw = peaks.peak(run["device"]["kind"])
    needed, config = run["cell"].family.needed, run["config"]
    need = (steps * config["num_hidden_layers"] * tokens
            * needed.index_key_bytes(config))
    return 100.0 * need / (seconds * bw)
