"""One run of one cell: set-up, the measured window, the check, the result.

Everything that belongs to one configuration, traffic mix or metric is data
found by name: ``BENCHMARK.json`` maps a cell to its configuration and mix,
a configuration is ``BENCHMARK.json``'s ``file`` for it, a mix is
``chipbench/traffic/<mix>.json``, a metric is ``chipbench/metrics/<name>.py``
(``compute(run) -> float | None``), a cell's correctness limit is
``chipbench/limits/<cell>.json`` and a configuration's plain reference is
``chipbench/reference/<reference>.py``.

The run drives ``runtime.Engine.run`` on a saturated backlog, as a busy
server or an offline batch job does. Set-up makes the weights on the chip
from the seed in one jitted call, builds the engine with its default page
size, prefill bucket and horizon, and warms every program the window can
call. The window opens once every slot holds a request with its first
token, and closes with the first step delivered ``seconds`` later; the run
stops there without draining (see ``timeline``). Nothing may compile inside it. The check then compares a
sample of the served tokens with the plain reference (see ``check``).
"""

from __future__ import annotations

import dataclasses
import gc
import importlib
import importlib.util
import json
import math
import shutil
import tempfile
import time
from functools import partial
from pathlib import Path

import numpy as np

from . import check, generator, peaks, timeline
from . import trace as trace_mod

MAX_RUN_SECONDS = 51          # the longest window a run may be asked for
# A traced run measures at most this long: the trace of a longer window
# takes longer to read than a run may last.
TRACE_SECONDS = 10.0
OPEN_WITHIN_S = 120.0         # from the backlog's start to the window's open


# --- finding things by name --------------------------------------------------


def load_benchmark(root: Path) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def load_config(root: Path, name: str) -> dict:
    for c in load_benchmark(root)["configs"]:
        if c["name"] == name:
            return json.loads((root / c["file"]).read_text())
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def load_traffic(root: Path, name: str) -> dict:
    return json.loads((root / "chipbench" / "traffic" / f"{name}.json")
                      .read_text())


def load_limits(root: Path, cell: str) -> dict:
    return json.loads((root / "chipbench" / "limits" / f"{cell}.json")
                      .read_text())


def load_metric(root: Path, name: str):
    path = root / "chipbench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reference(spec: dict):
    return importlib.import_module(f"chipbench.reference.{spec['reference']}")


@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    limits: dict
    chips: int
    end_to_end: list[str]
    per_layer: list[str]


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve_cell(root: Path, name: str) -> Cell:
    bm = load_benchmark(root)
    w = [w for w in bm["workloads"] if w["name"] == name]
    if not w:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = w[0]
    return Cell(
        name=name, config=load_config(root, w["config"]),
        traffic=load_traffic(root, w["traffic"]),
        limits=load_limits(root, name), chips=w["chips"],
        end_to_end=[m["name"] for m in bm["end_to_end"] if _applies(m, name)],
        per_layer=[m["name"] for m in bm["per_layer"] if _applies(m, name)])


# --- building the system under test -------------------------------------------


# Widths a family's program fixes in code and not in ModelConfig, each read
# off the last axis of one of its per-layer weights: key in the
# configuration's ``model`` -> (weight, streams sharing that axis).
FIXED_WIDTHS = {
    "ssm": {"ddlerp_lora_rank": ("mix_w1", 5),
            "decay_lora_rank": ("w_lora_a", 1)},
}


def model_config(spec: dict):
    """The program's ModelConfig from a configuration file. Keys of the
    file's ``model`` that ModelConfig has no field for are widths or
    constants the program fixes in code; the widths among them must be the
    program's, or the configuration is not what runs."""
    from repro.configs import ModelConfig, RecurrentConfig

    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    m = {k: v for k, v in spec["model"].items() if k in fields}
    if "recurrent" in m:
        r = dict(m["recurrent"])
        r["block_pattern"] = tuple(r["block_pattern"])
        m["recurrent"] = RecurrentConfig(**r)
    cfg = ModelConfig(name=spec["name"], **m)
    fixed = FIXED_WIDTHS.get(cfg.family, {})
    if fixed:
        import jax

        from repro.models import get_model

        blocks = jax.eval_shape(partial(get_model(cfg).init_params, cfg),
                                jax.random.PRNGKey(0))["blocks"]
        for key, (leaf, streams) in fixed.items():
            have = blocks[leaf].shape[-1] // streams
            if spec["model"].get(key) != have:
                raise ValueError(
                    f"{spec['name']}: the program runs {key} {have}, the "
                    f"configuration states {spec['model'].get(key)}")
    return cfg


def engine_config(cfg, spec: dict, traffic: dict, seed: int):
    """The cell fixes what a deployment fixes: slots, the longest context and
    the KV page budget; page size, prefill bucket and horizon are the
    engine's defaults."""
    from repro.runtime import EngineConfig
    from repro.runtime.kv_pager import PagerConfig

    default = EngineConfig()
    serving = spec["serving"]
    m = -(-serving["max_context"] // default.page_size) + 1
    kw = dict(num_slots=serving["num_slots"], max_pages_per_seq=m,
              greedy=True, seed=seed)
    if serving.get("kv_budget_bytes"):
        page_bytes = PagerConfig(2, default.page_size, m).page_bytes(cfg)
        kw["num_pages"] = 1 + serving["kv_budget_bytes"] // page_bytes
    return EngineConfig(**kw)


def make_params(cfg, mesh, seed: int):
    """The program's own float32 weights, made on the device from the seed
    in one jitted call, placed by the launcher's sharding rules."""
    import jax

    from repro.launch import sharding as sh
    from repro.models import get_model

    init = partial(get_model(cfg).init_params, cfg)
    key = jax.random.PRNGKey(seed)
    shardings = sh.to_shardings(
        sh.param_pspecs(jax.eval_shape(init, key), mesh), mesh)
    return jax.block_until_ready(jax.jit(init, out_shardings=shardings)(key))


def weight_read_bound(cfg, slots: int, pk: dict) -> float:
    """Most tokens per second the cell could deliver: every slot gets a token
    per step, and a step reads at least the weights once in bfloat16."""
    import jax

    from repro.models import get_model

    shapes = jax.eval_shape(partial(get_model(cfg).init_params, cfg),
                            jax.random.PRNGKey(0))
    n = sum(math.prod(a.shape) for a in jax.tree.leaves(shapes))
    return slots * pk["hbm_bytes_per_s"] / (n * peaks.BF16)


def backlog(cfg, traffic: dict, slots: int, seed: int, window,
            pk: dict) -> list:
    """Requests, all present at step 0, enough that the backlog cannot run
    dry within the longest window even at the weight-read bound."""
    from repro.runtime import Request

    need = weight_read_bound(cfg, slots, pk) * MAX_RUN_SECONDS
    per_block = slots * generator.mean_output(traffic, slots)
    lens = generator.lengths(traffic, slots, 2 + math.ceil(need / per_block))
    prompts = generator.prompts(lens, cfg.vocab_size, seed)
    return [Request(rid=i, prompt=p, max_new_tokens=o,
                    generated=timeline.TimedTokens(window, o))
            for i, (p, (_, o)) in enumerate(zip(prompts, lens))]


def warm_prompt_lengths(engine, traffic: dict) -> list[int]:
    """Every prompt length whose prefill program the window can call. A
    paged backend pads prompts to its bucket, and a request preempted for
    pages is prefilled again with its output so far, so every bucket from
    the shortest prompt's up to the longest context; a recurrent backend
    prefills each prompt length exactly, and has no pages to preempt for."""
    snap = traffic["prompt"]["snap_up"]
    if not engine.backend.paged:
        return sorted(snap)
    b = engine.ecfg.prefill_bucket
    longest = max(snap) + traffic["output"]["max"] - 1
    return list(range(-(-min(snap) // b) * b, -(-longest // b) * b + 1, b))


def warm(engine, traffic: dict, home) -> None:
    """Compile (or load from the cache) every program the window can run:
    each prefill length with one decode step after it (through
    ``Engine.run``), and the loop state's upload of every number of dirty
    slots from one to all, through ``DeviceLoopState``'s own ``touch`` and
    ``sync``, so whatever widths ``sync`` pads those to are the ones
    compiled."""
    import jax

    from repro.runtime import DeviceLoopState, Request

    rng = np.random.default_rng(0)
    V = engine.cfg.vocab_size
    reqs = [Request(rid=-1 - i, prompt=rng.integers(0, V, n).astype(np.int32),
                    max_new_tokens=2)
            for i, n in enumerate(warm_prompt_lengths(engine, traffic))]
    engine.run(reqs)
    B, M = engine.ecfg.num_slots, engine.ecfg.max_pages_per_seq
    ds = DeviceLoopState(B, M, home)
    zt, zv = np.zeros((B, M), np.int32), np.zeros((B,), np.int32)
    ds.sync(zt, zv, zv, zv)              # a new state starts all dirty
    for n in range(1, B + 1):
        for s in range(n):
            ds.touch(s)
        ds.sync(zt, zv, zv, zv)
    jax.block_until_ready(ds.table)


# --- the run -----------------------------------------------------------------


@dataclasses.dataclass
class Run:
    """What a metric reads. Times are host ``time.monotonic`` seconds."""
    cell: Cell
    model: dict                 # the configuration's ``model`` entry
    requests: list              # runtime.Request with TimedTokens
    open_t: float
    close_t: float              # the closing step's delivery
    setup_s: float
    live_at_open: int
    peaks: dict
    trace: trace_mod.DeviceTrace | None = None

    def deliveries(self):
        """(request, index of token, time) of every token delivered in the
        window, (open_t, close_t]."""
        for r in self.requests:
            for i, t in enumerate(r.generated.times):
                if self.open_t < t <= self.close_t:
                    yield r, i, t


class _Tracer:
    """Starts the profiler at the window's open, with a marker whose trace
    time ties the trace clock to the host clock."""

    def __init__(self):
        self.dir = None
        self.mark_t = None

    def start(self):
        import jax

        self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation("chipbench.open"):
            self.mark_t = time.monotonic()

    def stop(self):
        import jax
        jax.profiler.stop_trace()

    def read(self, t0: float, t1: float) -> trace_mod.DeviceTrace:
        try:
            path = next(Path(self.dir).rglob("*.xplane.pb"))
            tr = trace_mod.load(path)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        mark = tr.markers["chipbench.open"]
        tr.t0 = mark + (t0 - self.mark_t) * 1e9
        tr.t1 = mark + (t1 - self.mark_t) * 1e9
        return tr


def memory_line() -> str:
    import jax
    st = jax.devices()[0].memory_stats() or {}
    return (f"bytes_in_use={st.get('bytes_in_use')} "
            f"peak_bytes_in_use={st.get('peak_bytes_in_use')} "
            f"bytes_limit={st.get('bytes_limit')}")


def serve(cell: Cell, seed: int, seconds: float, *, trace: bool,
          t_start: float, pk: dict, log=print):
    """Set up, run the window, and stop. Returns (run, memory_peak_bytes);
    the program's state is freed before it returns."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.launch.compile_cache import use_compile_cache
    from repro.launch.mesh import make_host_mesh
    from repro.runtime import Engine

    log(f"compile cache: {use_compile_cache()}")
    cfg = model_config(cell.config)
    counter = timeline.CompileCounter()
    tracer = _Tracer() if trace else None

    def on_open():
        if tracer is not None:
            tracer.start()
        counter.armed = True

    slots = cell.config["serving"]["num_slots"]
    window = timeline.Window(
        slots, min(seconds, TRACE_SECONDS) if trace else seconds, on_open)
    mesh = make_host_mesh()
    with mesh:
        params = make_params(cfg, mesh, seed)
        log(f"weights: {memory_line()}")
        ecfg = engine_config(cfg, cell.config, cell.traffic, seed)
        engine = Engine(cfg, params, ecfg)
        log(f"engine: num_pages={ecfg.num_pages} {memory_line()}")
        warm(engine, cell.traffic, NamedSharding(mesh, PartitionSpec()))
        log(f"set-up: params + warm-up {time.monotonic() - t_start:.3f} s, "
            f"{memory_line()}")
        reqs = backlog(cfg, cell.traffic, slots, seed, window, pk)
        window.open_by = time.monotonic() + OPEN_WITHIN_S
        try:
            engine.run(reqs)
        except timeline.WindowClosed:
            pass
        else:
            raise RuntimeError("the backlog ran dry inside the window")
        finally:
            counter.armed = False
            if tracer is not None and tracer.dir is not None:
                tracer.stop()
    if counter.count:
        raise RuntimeError(f"{counter.count} compiles inside the window: "
                           f"{counter.names}")
    if window.live_at_open < slots:
        raise RuntimeError(f"the window opened with {window.live_at_open} "
                           f"of {slots} slots live")
    stats = jax.devices()[0].memory_stats() or {}
    memory_peak = int(stats.get("peak_bytes_in_use", 0))
    del engine, params
    gc.collect()
    run = Run(cell=cell, model=dict(cell.config["model"]), requests=reqs,
              open_t=window.open_t, close_t=window.close_t,
              setup_s=window.open_t - t_start,
              live_at_open=window.live_at_open, peaks=pk)
    log(f"window: live at open {window.live_at_open}, "
        f"{window.close_t - window.open_t:.3f} s")
    if tracer is not None:
        run.trace = tracer.read(run.open_t, run.close_t)
    return run, memory_peak


def run_cell(root: Path, name: str, seed: int, seconds: float, trace: bool,
             *, t_start: float, device: dict, pk: dict, log=print) -> dict:
    """The result line of one run (see ``run.py``)."""
    cell = resolve_cell(root, name)
    run, memory_peak = serve(cell, seed, seconds, trace=trace,
                             t_start=t_start, pk=pk, log=log)
    verdict = check.check(run, load_reference(cell.config), seed, log=log)
    names = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    bm = {m["name"]: m for m in (load_benchmark(root)["end_to_end"]
                                 + load_benchmark(root)["per_layer"])}
    for n in names:
        value = load_metric(root, n).compute(run)
        if value is not None:
            metrics[n] = {"value": value, "unit": bm[n]["unit"]}
    dev = dict(device, memory_peak_bytes=memory_peak)
    out = {"correct": verdict["correct"],
           "attempted": sum(1 for r in run.requests if len(r.generated)),
           "failed": sum(1 for r in run.requests if r.truncated),
           "metrics": metrics, "device": dev,
           "window": {"live_at_open": run.live_at_open,
                      "num_slots": cell.config["serving"]["num_slots"]}}
    if trace:
        tr = run.trace
        dev["busy_s"] = trace_mod.busy_ns(tr) / 1e9
        dev["window_s"] = tr.window_ns / 1e9
        out["breakdown"] = {"device_ops": trace_mod.top_ops(tr),
                            "idle_gaps": trace_mod.idle_gaps(tr)}
    out["checks"] = verdict["checks"]
    return out


def checkout_root() -> Path:
    return Path(__file__).resolve().parents[1]
