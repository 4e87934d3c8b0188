"""What every kind of cell shares: finding a cell's files by the names in
``BENCHMARK.json``, the chip gate, the compile cache, weights made on
the device from the seed in the layout the cell's family gives, the
compile count of a window, and the numbers compared for ``correct``."""

from __future__ import annotations

import functools
import importlib
import importlib.util
import json
import math
import os
import statistics
from typing import Any, Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


class NoChip(RuntimeError):
    """The measuring path found no accelerator, or too few chips."""


def load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    """Import one file by path (metric readers and kinds are found by
    the name in BENCHMARK.json, and a name may hold dots)."""
    name = "benchmark_file_" + os.path.basename(path).replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of ``workloads`` with the files it names."""

    def __init__(self, name: str, root: str = ROOT,
                 overrides: Optional[Dict[str, Any]] = None):
        bench = load_json(os.path.join(root, "BENCHMARK.json"))
        entry = next((w for w in bench["workloads"] if w["name"] == name),
                     None)
        if entry is None:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        conf = next(c for c in bench["configs"]
                    if c["name"] == entry["config"])
        here = os.path.join(root, "benchmark")
        self.root = root
        self.name = name
        self.chips = int(entry["chips"])
        self.bench = bench
        self.config = load_json(os.path.join(root, conf["file"]))
        self.spec = load_json(
            os.path.join(here, "workloads", name + ".json"))
        self.traffic = load_json(
            os.path.join(here, "traffic", entry["traffic"] + ".json"))
        for key, value in (overrides or {}).items():
            part, _, field = key.partition(".")
            getattr(self, part)[field] = value
        self.kind = self.spec["kind"]
        self.limits = self.spec["limits"]
        family = self.config.get("family")
        if not family:
            raise KeyError(f"{conf['file']} names no \"family\": the module "
                           f"under benchmark/families/ that knows its shape")
        self.family_file = os.path.join(here, "families", family + ".py")
        if not os.path.exists(self.family_file):
            raise FileNotFoundError(
                f"{conf['file']} names the family {family!r}, and there is "
                f"no {os.path.relpath(self.family_file, root)}")

    @functools.cached_property
    def family(self):
        """The configuration's family module: parameter tree, program
        config, engine and trainer hooks, reference, control and needed
        bytes (benchmark/families/decoder.py says what each is). Loaded
        at its first use, which in a run is the top of the kind's
        set-up: after the chip gate, so the program's imports it brings
        count in ``setup_s`` as they always did."""
        return load_module(self.family_file)

    @property
    def layout(self) -> Dict:
        """{path: (shape, std or None, stacked?)} of the parameter tree
        at this cell's configuration."""
        return self.family.param_layout(self.config)

    def reports(self, metric: Dict) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]

    def end_to_end(self) -> List[Dict]:
        return [m for m in self.bench["end_to_end"] if self.reports(m)]

    def per_layer(self) -> List[Dict]:
        """Per-layer metrics this cell may report: those that list it,
        and those without a list whose end-to-end metric it reports."""
        e2e = {m["name"] for m in self.end_to_end()}
        return [m for m in self.bench["per_layer"]
                if (self.name in m["workloads"] if "workloads" in m
                    else m["moves"] in e2e)]

    def for_rehearsal(self) -> None:
        """Swap in the family's tiny widths and the traffic file's
        rehearsal sizes."""
        self.config = self.family.rehearsal_config()
        self.traffic.update(self.traffic.get("rehearse", {}))
        self.spec.update(self.spec.get("rehearse", {}))
        self.limits = self.spec["limits"]


def load_kind(kind: str):
    return importlib.import_module("benchmark.kinds." + kind)


def read_per_layer(cell: Cell, run: Dict) -> Dict[str, Dict]:
    """Call each of the cell's per-layer readers; one that finds nothing
    to read returns None and is left out of the line."""
    out = {}
    for m in cell.per_layer():
        reader = load_module(os.path.join(
            cell.root, "benchmark", "metrics", m["name"] + ".py"))
        value = reader.read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# -- the device -------------------------------------------------------------


def start_jax(chips: int, rehearse: bool):
    """Compile cache on, then the chip gate. Returns (devices, record)."""
    import jax

    from edl_tpu.utils import jaxcache

    cache_dir = jaxcache.configure()
    # every program is kept, however quick its compile: set-up of a
    # second run in this checkout should find all of them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devs = jax.devices()
    if rehearse:
        if len(devs) < chips:
            raise NoChip(f"rehearsal needs {chips} devices, found "
                         f"{len(devs)}")
    elif devs[0].platform != "tpu" or len(devs) < chips:
        raise NoChip(
            f"need {chips} TPU chip(s); found {len(devs)} x "
            f"{devs[0].platform}. No result without the chip.")
    devs = devs[:chips]
    record = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    print(f"devices: {json.dumps(record)}; compile cache: {cache_dir}",
          flush=True)
    return devs, record


def memory_peak_bytes(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return int(max(peaks))


def kernels(rehearse: bool):
    """The Pallas interpreter for a rehearsal, the compiled kernel
    otherwise."""
    import contextlib

    if not rehearse:
        return contextlib.nullcontext()
    from edl_tpu.ops.flash_attention import interpret_kernels

    return interpret_kernels()


class CompileCount:
    """Compilations (a program loaded from the persistent cache is not
    one) and traces seen by JAX's own monitoring while open."""

    _listening = False
    _compiles = 0
    _traces = 0

    @classmethod
    def _listen(cls):
        if cls._listening:
            return
        import jax

        def on(name, _secs, **_kw):
            if name.endswith("backend_compile_duration"):
                cls._compiles += 1
            elif name.endswith("jaxpr_trace_duration"):
                cls._traces += 1

        def on_event(name, **_kw):
            # the backend-compile event wraps the cache lookup: a hit
            # fires both, so it is taken off again here
            if name.endswith("compilation_cache/cache_hits"):
                cls._compiles -= 1

        jax.monitoring.register_event_duration_secs_listener(on)
        jax.monitoring.register_event_listener(on_event)
        cls._listening = True

    def __enter__(self):
        self._listen()
        self._c0, self._t0 = self._compiles, self._traces
        self.compiles = self.traces = 0
        return self

    def __exit__(self, *exc):
        self.compiles = CompileCount._compiles - self._c0
        self.traces = CompileCount._traces - self._t0
        return False


# -- weights and tokens from the seed ---------------------------------------


def seed_key(seed: int):
    import jax

    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def layout_tree(layout: Dict, leaf) -> Dict:
    """The parameter tree with ``leaf(path, shape, std, stacked)`` at
    every path of ``layout`` (a family's ``param_layout``): nested
    dicts as deep as the paths are long."""
    tree: Dict = {}
    for path, spec in layout.items():
        node = tree
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = leaf(path, *spec)
    return tree


def initial_leaf(key, layout: Dict, path, dtype):
    """The seed's draw of one leaf; traced inside a jitted program."""
    import jax
    import jax.numpy as jnp

    shape, std, stacked = layout[tuple(path)]
    if std is None:
        return jnp.ones(shape, dtype)
    key = jax.random.fold_in(key, sorted(layout).index(tuple(path)))

    def draw(k, shp):
        return (jax.random.normal(k, shp, jnp.float32) * std).astype(dtype)

    if not stacked:
        return draw(key, shape)
    # a layer at a time: the float32 draw of a whole stacked leaf would
    # be a temporary twice the size of a bf16 export
    return jax.lax.map(lambda k: draw(k, shape[1:]),
                       jax.random.split(key, shape[0]))


def make_params(seed: int, layout: Dict, dtype, shardings=None):
    """The whole tree in one jitted call, on the device, in ``dtype``."""
    import jax

    def build(key):
        return layout_tree(
            layout, lambda path, *_: initial_leaf(key, layout, path, dtype))

    return jax.jit(build, out_shardings=shardings)(seed_key(seed))


# -- correct ----------------------------------------------------------------


class Compared:
    """The numbers compared for ``correct``, each beside its limit."""

    def __init__(self):
        self.rows: List[Dict] = []

    def add(self, name: str, value: float, limit: float) -> None:
        ok = value == value and value <= limit
        self.rows.append({"name": name, "value": value, "limit": limit,
                          "ok": bool(ok)})
        print(f"compared {name}: {value:.6g} (limit {limit:.6g}) "
              f"{'ok' if ok else 'FAILED'}", flush=True)

    @property
    def correct(self) -> bool:
        return bool(self.rows) and all(r["ok"] for r in self.rows)


def worst_leaf_gap(program: Dict[str, float], reference: Dict[str, float]):
    """Per-leaf norms (given as sums of squares): the largest gap
    between the program's norm and the reference's, measured against
    the reference's norm of that leaf or of the median leaf, whichever
    is larger. Returns (gap, leaf)."""
    ref = {k: v ** 0.5 for k, v in reference.items()}
    floor = statistics.median(ref.values())
    worst, where = 0.0, ""
    for k, r in ref.items():
        p = max(program[k], 0.0) ** 0.5
        gap = abs(p - r) / max(r, floor)
        if gap != gap:  # a NaN is the worst there is
            return gap, k
        if gap > worst:
            worst, where = gap, k
    return worst, where


def percentile(values: List[float], q: float) -> float:
    """Nearest rank: the smallest value with q of the sample at or
    under it."""
    s = sorted(values)
    return s[max(0, math.ceil(q * len(s) - 1e-9) - 1)]
