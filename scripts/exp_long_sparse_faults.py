"""Does ``glm5.long-sparse``'s comparison catch a fault of the sparse
latent-attention model? Each fault is planted in the PROGRAM
(``edl_tpu.models.glm_dsa``: by patching the functions the model calls
into, or the program's config where the fault is a constant of the
layer); the reference is its own code and is left alone. Two ways to
read it, the same statistic (how far the token the program puts first
lies under the reference's best):

- by the cell (default): the cell's set-up, a window, the cell's own
  check, a fault and seed, as ``benchmark.readings`` makes them
  (``--control`` switches the int8 path on too). Three minutes a
  reading. ``--requests N`` compares ``N`` requests in place of the
  cell's ``check_requests`` and prints the statistic a request at a
  time; one ``--draw`` reads another draw of the weights by the cell;
- ``--forward T``: ``glm_dsa.forward`` over T positions of one seeded
  sequence at the cell's widths against the reference's logits, every
  position compared (teacher-forced), the first ``index_topk`` positions
  (every key attended) apart from the rest (the indexer chooses). A few
  seconds a reading once compiled; ``--draw EMBED,QUERY_UP,ATTN_OUT``
  (the family's ``*_STD`` constants) reads other draws of the weights
  (how those constants were set: PERF.md sections 2 and 6).

    PYTHONPATH=. python3 scripts/exp_long_sparse_faults.py \\
        [--only sound,every_key_attended] [--seeds 1,2] [--control] \\
        [--requests 6] \\
        [--forward 8192 [--draw 0.02,1,4 --draw 1,1,1]] [--rehearse]
"""

import argparse
import dataclasses
import gc
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import harness, run
from edl_tpu.models import glm_dsa as g
from edl_tpu.serving import engine

CELL = "glm5.long-sparse"
SOUND = {name: getattr(g, name) for name in (
    "select_mask", "index_scores", "_rope_first",
    "_query_rank")}


def first_mask(scores, valid, k):
    """The first ``k`` positions in place of the ``k`` best."""
    return valid & (jnp.arange(scores.shape[-1]) < k)


def no_relu(qi, w, ki):
    s = jnp.einsum("bthd,bsd->bths", qi, ki,
                   preferred_element_type=jnp.float32)
    return jnp.sum(s * w[..., None], axis=2) + 0.0


# name -> (module functions replaced, config fields replaced)
FAULTS = {
    "sound": ({}, {}),
    "every_key_attended": ({}, {"index_topk": 1 << 20}),
    "first_positions_not_the_top": ({"select_mask": first_mask}, {}),
    "relu_left_out": ({"index_scores": no_relu}, {}),
    "index_weights_left_out": ({"index_scores": lambda qi, w, ki: SOUND[
        "index_scores"](qi, jnp.ones_like(w), ki)}, {}),
    "index_key_rope_left_out": ({"_rope_first": lambda cfg, x, positions: (
        x if x.shape[2] == 1 else SOUND["_rope_first"](cfg, x, positions))},
        {}),
    "query_norm_left_out": ({"_query_rank": lambda cfg, a, lp: g._ll._matw(
        a, lp["wqa"])}, {}),
    "routed_scaling_factor_left_out": ({}, {"route_scale": 1.0}),
}


def set_draw(fam, draw: str) -> None:
    """"EMBED,QUERY_UP,ATTN_OUT" into the family's constants, which its
    ``param_layout`` reads when the weights are drawn."""
    fam.EMBED_STD, fam.QUERY_UP_STD, fam.ATTN_OUT_STD = map(
        float, draw.split(","))


def plant(name: str):
    """Put the fault's functions into the model's module (the sound
    ones elsewhere) and give its config fields."""
    patched, fields = FAULTS[name]
    for attr, fn in SOUND.items():
        setattr(g, attr, patched.get(attr, fn))
    engine._programs.clear()  # traced with the last fault in them
    return fields


def by_the_cell(cell, devices, args) -> None:
    sound_config = cell.family.program_config
    for seed, name in ((int(s), name) for s in args.seeds.split(",")
                       for name in args.only.split(",") if name):
        fields = plant(name)
        cell.family.program_config = lambda *a, fields=fields, **kw: \
            dataclasses.replace(sound_config(*a, **kw), **fields)
        one = argparse.Namespace(
            seed=seed, seconds=args.seconds, control=args.control,
            rehearse=args.rehearse, describe_trace=False, trace=0)
        kind = harness.load_kind(cell.kind).Kind(
            run.Context(cell, one, devices))
        compared = harness.Compared()
        with harness.kernels(args.rehearse):
            kind.setup()
            kind.window(one.seconds)
            kind.release()
            if args.requests:
                by_request(cell, kind, args.requests, compared)
            else:
                kind.check(compared)
        print("FAULT " + json.dumps({
            "fault": name, "seed": seed, "control": args.control,
            "correct": compared.correct, "tokens": kind.counters["tokens"],
            "tokens_per_s": kind.counters["tokens"]
            / kind.counters["window_s"],
            "engine_steps": kind.counters["engine_steps"],
            "failed": kind.failed,
            "compared_requests": [
                [len(kind.sent[r].prompt), len(kind.finished[r])]
                for r in kind.sample()],
            "rows": {r["name"]: r["value"] for r in compared.rows}}),
            flush=True)
        del kind
        gc.collect()


def by_request(cell, kind, n: int, compared) -> None:
    """``kinds/serve.py: check`` with ``check_requests`` = ``n``, a
    request at a time where the cell gives the total alone: what one
    more compared request adds, and whether an answer repeats itself."""
    config, max_len = cell.config, int(cell.spec["engine"]["max_len"])
    logits = cell.family.reference_logits
    if kind.params is None:  # the control's served tree took its place
        kind.params = harness.make_params(
            kind.ctx.seed, cell.layout, jnp.bfloat16)

    @jax.jit
    def gaps(params, tokens, served):
        lg = logits(params, tokens, config)
        at = jnp.take_along_axis(
            lg, jnp.maximum(served, 0)[:, None], 1)[:, 0]
        return jnp.where(served >= 0, jnp.max(lg, axis=-1) - at, 0.0)

    cell.spec["check_requests"], before = n, cell.spec["check_requests"]
    picked = kind.sample()
    cell.spec["check_requests"] = before
    every = []
    for i, rid in enumerate(picked):
        prompt, out = kind.sent[rid].prompt, kind.finished[rid]
        tokens = np.zeros(max_len, np.int32)
        served = np.full(max_len, -1, np.int32)
        seq = prompt + out[:-1]
        tokens[:len(seq)] = seq
        served[len(prompt) - 1:len(prompt) - 1 + len(out)] = out
        t0 = time.perf_counter()
        g = np.asarray(gaps(kind.params, tokens, served))[served >= 0]
        every.extend(g.tolist())
        _, counts = np.unique(out, return_counts=True)
        print("REQUEST " + json.dumps({
            "prompt": len(prompt), "answer": len(out),
            "reference_s": round(time.perf_counter() - t0, 1),
            "not_first": int((g > 0).sum()), "gap_mean": float(g.mean()),
            "gap_max": float(g.max()), "distinct_tokens": len(counts),
            "commonest_token_share": float(counts.max() / len(out)),
            "so_far": {"requests": i + 1,
                       "tokens": len(every),
                       "gap_mean": float(np.mean(every)),
                       "gap_max": float(np.max(every))}}), flush=True)
    for name, value in (("served_token_gap_max", np.max(every)),
                        ("served_token_gap_mean", np.mean(every))):
        compared.add(name, float(value), cell.limits[name])


def by_forward(cell, args) -> None:
    fam, t = cell.family, args.forward
    config = cell.config
    draws = args.draw or [
        f"{fam.EMBED_STD},{fam.QUERY_UP_STD},{fam.ATTN_OUT_STD}"]
    reference = jax.jit(lambda p, tk: fam.reference_logits(p, tk, config))
    programs = {}
    for draw in draws:
        set_draw(fam, draw)
        for seed in (int(s) for s in args.seeds.split(",")):
            params = harness.make_params(
                seed, fam.param_layout(config), jnp.bfloat16)
            tokens = jnp.asarray(np.random.default_rng(seed).integers(
                0, config["vocab_size"], (1, t), dtype=np.int32))
            t0 = time.time()
            ref = np.asarray(reference(params, tokens[0]))
            ref_s = time.time() - t0
            for name in args.only.split(","):
                for control in ((False, True) if args.control
                                and name == "sound" else (False,)):
                    if (name, control) not in programs:
                        cfg = dataclasses.replace(fam.program_config(
                            config, training=False), **plant(name))
                        programs[name, control] = jax.jit(
                            lambda p, tk, cfg=cfg: g.forward(p, tk, cfg))
                    served = fam.control_params(params) if control else params
                    with harness.kernels(args.rehearse):
                        got = np.asarray(
                            programs[name, control](served, tokens))[0]
                    first = got.argmax(-1)
                    gap = ref.max(-1) - ref[np.arange(t), first]
                    diff = np.abs(got - ref).max(-1)
                    k = min(config["index_topk"], t - 1)
                    # how a run's few hundred tokens would spread: the
                    # mean over each 512 positions the indexer chooses
                    parts = [float(c.mean()) for c in np.array_split(
                        gap[k:], max((t - k) // 512, 1))]
                    print("FORWARD " + json.dumps({
                        "draw": draw, "seed": seed, "fault": name,
                        "control": control, "positions": t,
                        "reference_s": round(ref_s, 1),
                        "attended_all": {
                            "gap_mean": float(gap[:k].mean()),
                            "gap_max": float(gap[:k].max()),
                            "logit_diff_mean": float(diff[:k].mean())},
                        "chosen": {
                            "gap_mean": float(gap[k:].mean()),
                            "gap_max": float(gap[k:].max()),
                            "not_first": int((gap[k:] > 0).sum()),
                            "gap_mean_by_512": [min(parts), max(parts)],
                            "logit_diff_mean": float(diff[k:].mean())}}),
                        flush=True)
    plant("sound")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seeds", default="4100000137")
    ap.add_argument("--only", default=",".join(FAULTS))
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--requests", type=int, default=0)
    ap.add_argument("--forward", type=int, default=0)
    ap.add_argument("--draw", action="append")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    cell = harness.Cell(CELL)
    if args.rehearse:
        cell.for_rehearsal()
    devices, _ = harness.start_jax(cell.chips, args.rehearse)
    if args.forward:
        by_forward(cell, args)
    else:
        if args.draw:  # one other draw of the weights, by the cell
            set_draw(cell.family, args.draw[0])
        by_the_cell(cell, devices, args)


if __name__ == "__main__":
    main()
