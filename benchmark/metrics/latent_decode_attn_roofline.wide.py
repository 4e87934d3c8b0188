"""Latent rows the kernel's calls traced had to read (the tokens
resident x the bytes of one latent row, a call: one layer of one decode
step) over (the device time of ``edl_decode_attn_latent``, the Mosaic
kernel under the ``attn.latent_absorb`` scope x the chip's HBM peak).
Bound: memory. The kernel fetches whole S-blocks of padded rows; the
needed bytes are the live positions' unpadded rows."""

from benchmark.reduce import mla_moe, peaks


def read(run):
    timed = mla_moe.latent_kernel(run)
    if run["device"]["platform"] != "tpu" or not timed or not timed[0]:
        return None
    seconds, calls = timed
    _, bw = peaks.peak(run["device"]["kind"])
    need = (calls * run["counters"]["resident_tokens_mean"]
            * run["cell"].family.needed.latent_bytes_per_token(run["config"]))
    return 100.0 * need / (seconds * bw)
