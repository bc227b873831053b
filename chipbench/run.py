#!/usr/bin/env python3
"""Serve one benchmark cell on the chip and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

A cell is an entry of ``workloads`` in ``BENCHMARK.json``: a model
configuration (``chipbench/configs/<config>.json``) under a traffic mix
(``chipbench/traffic/<traffic>.json``).  A configuration may bring a
module beside its file, ``chipbench/configs/<config>.py``, with its own
``arch_of``, ``program_config``, ``gaps`` and ``model_flops``
(``Model``); each it leaves out is the default every other configuration
uses.  One process per run:

 1. compile cache at ``.jax_cache/`` in the checkout, whatever
    ``JAX_COMPILATION_CACHE_DIR`` says, so that two checkouts never share
    one; the device must be a TPU of a kind in ``peaks.py``, as many as
    the cell asks for;
 2. weights drawn on the device from the seed (``weights.py``);
 3. every shape the cell's traffic can reach is compiled and run once;
 4. an unscored lead-in of the cell's own traffic;
 5. the measured window, on the host's clock, then a bounded drain of
    the requests due in it;
 6. the program's state is freed and a sample of the finished requests
    is checked against the float32 reference (the configuration's
    ``gaps``, by default ``check.gaps``).

``setup_s`` runs from process start to the start of the lead-in.  With
``--trace 1`` the last ``trace_seconds`` of the window run under the
profiler, and the line carries the per-layer metrics, each read by its
own reader under ``chipbench/metrics/``; with ``--trace 0`` it carries
the cell's end-to-end metrics.  The last line of standard output is one
JSON object; the numbers compared for ``correct`` are printed beside
their limits as the last lines of standard error and, under ``checks``,
last in that object.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Callable, Dict, List, Optional  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))

import numpy as np  # noqa: E402

import gen  # noqa: E402

DRAIN_S = 60.0


class Refused(RuntimeError):
    """The run cannot be made here: no result is printed."""


# ---------------------------------------------------------------------------
# the cell, from data files
# ---------------------------------------------------------------------------
def _json(path: Path) -> Dict:
    if not path.is_file():
        raise Refused(f"missing {path}")
    return json.loads(path.read_text())


def load_cell(name: str, root: Path = ROOT) -> Dict:
    bench = _json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg_file = root / cfg_entry["file"]
    return {
        "cell": cell,
        "config": _json(cfg_file),
        "module": cfg_file.with_suffix(".py"),
        "mix": _json(root / "chipbench" / "traffic"
                     / f"{cell['traffic']}.json"),
        "end_to_end": [m for m in bench["end_to_end"]
                       if name in m.get("workloads", [name])],
        "per_layer": [m for m in bench["per_layer"]
                      if name in m.get("workloads", [name])],
        "run_seconds": bench["run_seconds"],
    }


def arch_of(config: Dict) -> Dict:
    """The plain architecture numbers of a configuration file."""
    d = config["hidden_size"]
    h = config["num_attention_heads"]
    experts = config.get("num_local_experts", 0)
    return {
        "n_layers": config["num_hidden_layers"], "d_model": d,
        "n_heads": h, "n_kv_heads": config["num_key_value_heads"],
        "head_dim": config.get("head_dim") or d // h,
        "vocab_size": config["vocab_size"],
        "norm_eps": config.get("rms_norm_eps",
                               config.get("layer_norm_eps", 1e-5)),
        "rope_theta": float(config["rope_theta"]),
        "tie_embeddings": bool(config["tie_word_embeddings"]),
        "ffn": ({"kind": "moe", "d_ff": config["intermediate_size"],
                 "n_experts": experts,
                 "top_k": config["num_experts_per_tok"]} if experts else
                {"kind": "dense", "d_ff": config["intermediate_size"]}),
    }


# ---------------------------------------------------------------------------
# the program
# ---------------------------------------------------------------------------
def import_program(root: Path = ROOT) -> None:
    src = root / "src"
    if not (src / "repro").is_dir():
        raise Refused(f"no program under {src}")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))


def program_config(config: Dict):
    """The program's ArchConfig with every number taken from the file."""
    import dataclasses
    from repro.configs import get_config
    a = arch_of(config)
    base = get_config(config["arch_id"])
    f = a["ffn"]
    return base.replace(
        n_layers=a["n_layers"], d_model=a["d_model"],
        vocab_size=a["vocab_size"], norm_eps=a["norm_eps"],
        rope_theta=a["rope_theta"], tie_embeddings=a["tie_embeddings"],
        attention=dataclasses.replace(
            base.attention, n_heads=a["n_heads"],
            n_kv_heads=a["n_kv_heads"], head_dim=a["head_dim"]),
        ffn=dataclasses.replace(base.ffn, d_ff=f["d_ff"],
                                n_experts=f.get("n_experts", 0),
                                top_k=f.get("top_k", 0)))


def load_module(path: Path, name: str):
    """The Python file ``path``, imported under ``name``."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass(frozen=True)
class Model:
    """How a configuration's model is read, built, checked and counted.

    ``arch_of(config)``: the architecture numbers, with at least the
    keys the readers use (``n_layers``, ``d_model``, ``n_heads``,
    ``n_kv_heads``, ``head_dim``, ``vocab_size``, ``ffn``).
    ``program_config(config)``: the program's ``ArchConfig``.
    ``gaps(arch, seed, finished, control)``: the numbers ``correct``
    compares, as ``check.gaps`` returns them, from ``finished``
    entries ``(prompt, served tokens, the program's Request)``.
    ``model_flops(arch, contexts)``: the FLOPs of tokens received at
    those context lengths."""
    arch_of: Callable
    program_config: Callable
    gaps: Callable
    model_flops: Callable


def model_of(cell: Dict) -> Model:
    """The functions of the configuration's module (``cell["module"]``,
    beside its file) where it has one, and the defaults for the rest."""
    import check
    import counts
    fns = {"arch_of": arch_of, "program_config": program_config,
           "gaps": check.gaps, "model_flops": counts.model_flops}
    path = cell["module"]
    if path.is_file():
        mod = load_module(path, "chipbench_config_"
                          + path.stem.replace(".", "_"))
        fns.update({k: getattr(mod, k) for k in fns if hasattr(mod, k)})
    return Model(**fns)


def enable_cache(root: Path = ROOT) -> None:
    import jax
    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def check_device(chips: int) -> Dict:
    import jax
    import peaks
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"no TPU: JAX's first device is {devs[0].platform!r}")
    if len(devs) < chips:
        raise Refused(f"{len(devs)} TPU devices, the cell needs {chips}")
    peaks.for_kind(devs[0].device_kind)        # unknown kinds refuse
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


class CompileClock(logging.Handler):
    """Executables built while open, from JAX's monitoring events (as
    the program's ``chip_smoke.CompileClock`` counts them), and the names
    of the functions JAX had to compile or load, from its compile log."""

    BACKEND = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        super().__init__(logging.DEBUG)
        self.compiles = 0
        self.names: List[str] = []

    def __call__(self, event: str, duration: float, **_kw) -> None:
        if event == self.BACKEND:
            self.compiles += 1

    def emit(self, record: logging.LogRecord) -> None:
        msg = record.getMessage()
        if msg.startswith("Compiling "):
            self.names.append(msg.split(" ", 2)[1])

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self)
        self._log = jax.config.jax_log_compiles
        jax.config.update("jax_log_compiles", True)
        logging.getLogger("jax").addHandler(self)
        return self

    def __exit__(self, *exc) -> None:
        import jax
        logging.getLogger("jax").removeHandler(self)
        jax.config.update("jax_log_compiles", self._log)
        jax.monitoring.unregister_event_duration_listener(self)


def build_engine(config: Dict, seed: int, model: Model):
    import jax
    from repro.models import init_model
    from repro.serving import DecodeEngine, PagedKVConfig
    import weights
    cfg = model.program_config(config)
    shapes = jax.eval_shape(lambda k: init_model(k, cfg),
                            jax.random.PRNGKey(0))
    params = jax.block_until_ready(weights.build(shapes, seed))
    e = config["engine"]
    engine = DecodeEngine(cfg, params, batch=e["slots"],
                          max_len=e["max_len"], use_kernel=True,
                          paged=PagedKVConfig(block_size=e["block"]))
    return engine


def new_loop(engine, mix: Dict, config: Dict):
    from repro.serving import ServingLoop
    e = config["engine"]
    return ServingLoop(engine, mode=mix["mode"], eps=e["eps"],
                       max_width=e["max_width"])


# ---------------------------------------------------------------------------
# warm-up: every shape the traffic can reach
# ---------------------------------------------------------------------------
def active_counts(mix: Dict, slots: int) -> List[int]:
    """Active rows a decode step can see.  A closed loop with at least as
    many clients as slots refills every freed slot before the next step."""
    if mix["loop"] == "closed" and mix["clients_per_slot"] >= 1:
        return [slots]
    return list(range(1, slots + 1))


def decode_widths(loop, mix: Dict, slots: int) -> List[int]:
    """Widths the adapter picks for the budgets of every context length."""
    eng = loop.engine
    budgets = {eng.nfp_budget(loop.eps, ell=ell)
               for ell in range(1, eng.max_len + 1)}
    return sorted({loop.adapter.width(n, b) for b in budgets
                   for n in active_counts(mix, slots)})


def _pow2(x: int, floor: int = 8) -> int:
    m = floor
    while m < x:
        m *= 2
    return m


def prefill_groups(lo: int, hi: int, slots: int, max_len: int) -> List[List[int]]:
    """Prompt-length groups that together reach every prefill bucket,
    every scatter bucket and every group size an admission can form
    from prompts of ``lo..hi`` tokens (``DecodeEngine`` pads a group to
    the power-of-two bucket of its longest prompt, and its scatter to
    the power-of-two above its total)."""
    by_bucket: Dict[int, List[int]] = {}
    for p in range(lo, hi + 1):
        by_bucket.setdefault(min(_pow2(p), max_len), []).append(p)
    groups, seen_pairs, seen_k = [], set(), set()
    for k in range(1, slots + 1):
        for bucket, ps in sorted(by_bucket.items()):
            p_lo, p_hi = ps[0], ps[-1]
            t_lo, t_hi = p_lo + (k - 1) * lo, k * p_hi
            m = _pow2(t_lo)
            while m <= _pow2(t_hi):
                if (bucket, m) in seen_pairs and k in seen_k:
                    m *= 2
                    continue
                # a total in (m/2, m]: the longest prompt first, the rest
                # filled as evenly as the bounds allow
                total = min(max(m if m > 8 else t_lo, t_lo), t_hi)
                first = min(p_hi, total - (k - 1) * lo)
                rest, left = [], total - first
                for i in range(k - 1):
                    take = min(first, left - (k - 2 - i) * lo)
                    rest.append(take)
                    left -= take
                group = [first] + rest
                if (_pow2(total) == m and first >= p_lo
                        and all(lo <= x <= first for x in rest)):
                    groups.append(group)
                    seen_pairs.add((bucket, m))
                    seen_k.add(k)
                m *= 2
    return groups


def warm_up(engine, mix: Dict, config: Dict, seed: int) -> Dict:
    import jax
    from repro.serving.engine import greedy_tokens
    slots = config["engine"]["slots"]
    loop = new_loop(engine, mix, config)
    widths = decode_widths(loop, mix, slots)
    for w in widths:
        # the step's own entry, host tokens and all, the per-row read of
        # its hidden states the adapters make, and a commit that advances
        # no row (every row is free: its writes went to the trash page)
        logits, cache, hidden = loop.shared_forward(
            np.zeros((slots, w), np.int64), w)
        np.asarray(greedy_tokens(logits))
        jax.block_until_ready(hidden[0])
        engine.commit_slots(cache, np.zeros((slots,), np.int64))
        # hold no pool here: admission below replaces the engine's
        del logits, cache, hidden
    rng = np.random.default_rng([int(seed), 3])
    vocab = engine.cfg.vocab_size
    groups = prefill_groups(mix["prompt"]["lo"], mix["prompt"]["hi"],
                            slots, config["engine"]["max_len"])
    for group in groups:
        loop = new_loop(engine, mix, config)
        for p in group:
            loop.submit(rng.integers(0, vocab, p), 1)
        loop.admit()
        for s in list(loop.active):
            engine.release_slot(s)
    jax.block_until_ready(engine.cache)
    return {"widths": widths, "prefill_groups": len(groups)}


# ---------------------------------------------------------------------------
# the driver: lead-in, window, drain, on the host's clock
# ---------------------------------------------------------------------------
@dataclass
class Rec:
    rid: int
    due: float
    prompt_len: int
    max_tokens: int
    admit: float = math.nan
    first: float = math.nan
    last: float = math.nan
    n: int = 0
    req: object = None


@dataclass
class Emission:
    t: float
    rid: int
    n: int
    contexts: List[int]


@dataclass
class StepRec:
    t: float
    width: int
    lens: np.ndarray


@dataclass
class Run:
    w0: float = 0.0
    w1: float = 0.0
    recs: Dict[int, Rec] = field(default_factory=dict)
    emissions: List[Emission] = field(default_factory=list)
    steps: List[StepRec] = field(default_factory=list)
    admits: List[tuple] = field(default_factory=list)  # (t, prompt tokens)
    late: List[float] = field(default_factory=list)
    trace_t: tuple = (math.nan, math.nan)
    compiles_window: int = 0
    compiles_lead: int = 0
    compiled_in_window: List[str] = field(default_factory=list)
    compiled_in_lead: List[str] = field(default_factory=list)

    def tokens_between(self, t0: float, t1: float) -> int:
        return sum(e.n for e in self.emissions if t0 <= e.t <= t1)

    def forwards_between(self, t0: float, t1: float) -> int:
        return sum(1 for s in self.steps if t0 <= s.t <= t1)

    def due_in_window(self) -> List[Rec]:
        return [r for r in self.recs.values() if self.w0 <= r.due < self.w1]


def _span(name: str):
    import jax
    return jax.profiler.TraceAnnotation("chipbench." + name)


class Driver:
    """Feeds a ServingLoop from the mix and timestamps what the host sees.

    Open loop: each request is submitted once it is due (due times from
    the start of the lead-in).  Closed loop: ``clients`` callers, each
    sending its next request the moment its last one finished.  A token
    is stamped when ``admit`` or ``step`` returns it to the host."""

    def __init__(self, loop, mix: Dict, requests: List[gen.Request],
                 clients: int, clock=time.perf_counter):
        self.loop = loop
        self.mix = mix
        self.todo = list(requests)
        self.next = 0
        self.clients = clients
        self.clock = clock
        self.run = Run()
        self.inflight: Dict[int, Rec] = {}
        self.t0 = 0.0

    def _submit(self, r: gen.Request, now: float, due: float) -> None:
        rec = Rec(r.rid, due, len(r.prompt), r.max_tokens)
        rec.req = self.loop.submit(r.prompt, r.max_tokens)
        self.run.recs[r.rid] = rec
        self.inflight[r.rid] = rec
        if self.mix["loop"] == "open":
            self.run.late.append(now - due)

    def _arrivals(self, now: float, stop: float) -> None:
        if self.mix["loop"] == "open":
            while (self.next < len(self.todo)
                   and self.t0 + self.todo[self.next].due_s <= now
                   and self.t0 + self.todo[self.next].due_s < stop):
                r = self.todo[self.next]
                self._submit(r, now, self.t0 + r.due_s)
                self.next += 1
        else:
            while len(self.inflight) < self.clients and now < stop:
                if self.next >= len(self.todo):
                    raise RuntimeError("closed loop ran out of requests")
                self._submit(self.todo[self.next], now, now)
                self.next += 1

    def _observe(self, now: float, admitted: bool) -> None:
        for rid in list(self.inflight):
            rec = self.inflight[rid]
            req = rec.req
            if admitted and math.isnan(rec.admit) and req.slot is not None:
                rec.admit = self._admit_t
                self.run.admits.append((self._admit_t, rec.prompt_len))
            k = min(len(req.generated), rec.max_tokens)
            if k > rec.n:
                ctx = [rec.prompt_len + j for j in range(rec.n, k)]
                self.run.emissions.append(Emission(now, rid, k - rec.n, ctx))
                if rec.n == 0:
                    rec.first = now
                rec.last = now
                rec.n = k
            if req.done:
                del self.inflight[rid]

    def _admit(self) -> None:
        self._admit_t = self.clock()
        with _span("admit"):
            n = self.loop.admit()
        if n:
            self._observe(self.clock(), True)

    def _step(self) -> None:
        log = self.loop.step_log
        mark = len(log)
        lens = self.loop.engine.slot_lens_host.copy()
        with _span("step"):
            self.loop.step()
        now = self.clock()
        for e in log[mark:]:
            self.run.steps.append(StepRec(now, e["width"], lens))
        self._observe(now, False)

    def _wait(self, until: float) -> None:
        with _span("wait"):
            time.sleep(max(0.0, until - self.clock()))

    def _next_due(self) -> float:
        if self.mix["loop"] == "open" and self.next < len(self.todo):
            return self.t0 + self.todo[self.next].due_s
        return math.inf

    def serve(self, lead_s: float, window_s: float, trace_s: float = 0.0,
              trace_dir: Optional[str] = None) -> Run:
        """Lead-in, then the window.  With ``trace_dir`` the last
        ``trace_s`` seconds of the window run under the profiler, whose
        stop (many seconds of collecting the trace) then falls after the
        window's close, not inside it."""
        import jax
        run = self.run
        self.t0 = self.clock()
        run.w0 = self.t0 + lead_s
        run.w1 = run.w0 + window_s
        trace_at = run.w1 - trace_s if trace_dir is not None else math.inf
        tracing = None
        lead_compiles, lead_names = None, 0
        with CompileClock() as clock:
            while True:
                now = self.clock()
                if now >= run.w0 and lead_compiles is None:
                    lead_compiles, lead_names = clock.compiles, len(clock.names)
                if now >= trace_at and now < run.w1:
                    # host spans only: the Python tracer records every
                    # call and slows a host-bound step by several %
                    opts = jax.profiler.ProfileOptions()
                    opts.python_tracer_level = 0
                    jax.profiler.start_trace(trace_dir, profiler_options=opts)
                    tracing = _span("window").__enter__()
                    run.trace_t = (now, math.nan)
                    trace_at = math.inf
                if now >= run.w1:
                    break
                with _span("arrivals"):
                    self._arrivals(now, run.w1)
                self._admit()
                if self.loop.active:
                    self._step()
                else:
                    self._wait(min(self._next_due(), trace_at, run.w1))
        if tracing is not None:
            tracing.__exit__(None, None, None)
            run.trace_t = (run.trace_t[0], self.clock())
            jax.profiler.stop_trace()
        run.compiles_window = clock.compiles - lead_compiles
        run.compiles_lead = lead_compiles
        run.compiled_in_window = clock.names[lead_names:]
        run.compiled_in_lead = clock.names[:lead_names]
        if self.mix["loop"] == "open":
            self._drain(self.clock() + DRAIN_S)
        return run

    def _drain(self, deadline: float) -> None:
        due = {r.rid for r in self.run.due_in_window()}
        with _span("drain"):
            while any(rid in self.inflight for rid in due) \
                    and self.clock() < deadline:
                self._admit()
                if self.loop.active:
                    self._step()


def plan(mix: Dict, config: Dict, seed: int, seconds: float, vocab: int):
    """Requests for the run, and the closed loop's client count."""
    slots = config["engine"]["slots"]
    lead = float(mix["lead_in_s"])
    if mix["loop"] == "open":
        rate = float(mix["arrivals"]["rate_rps"])
        if mix["arrivals"]["kind"] == "mmpp":
            rate = float(mix["arrivals"]["burst_rate_rps"])
        n = int(math.ceil(rate * (lead + seconds) * 1.5)) + 16
        return gen.generate(mix, seed, n, vocab), 0
    clients = int(round(mix["clients_per_slot"] * slots))
    return gen.generate(mix, seed, int(mix["pool"]), vocab), clients


# ---------------------------------------------------------------------------
# the result
# ---------------------------------------------------------------------------
def end_to_end(run: Run, seconds: float, setup_s: float) -> Dict[str, float]:
    return {"setup_s": setup_s,
            "output_tok_per_s": run.tokens_between(run.w0, run.w1) / seconds}


def load_reader(name: str):
    return load_module(HERE / "metrics" / f"{name}.py",
                       "chipbench_metric_" + name.replace(".", "_")).read


@dataclass
class Context:
    """What a per-layer reader may read."""
    run: Run
    arch: Dict
    config: Dict
    peaks: object
    trace: object        # trace_reduce.Reduced, or None
    model: Model


def finished_requests(run: Run, upto: float):
    """(prompt, served tokens, the program's Request) of requests
    finished by ``upto``.  The Request is the program's own record of
    how it served them; its only device array is the MTP adapter's
    ``hidden``, a row of its own, so it keeps none of the program's
    state alive."""
    out = []
    for r in run.recs.values():
        if r.n == r.max_tokens and r.last <= upto:
            out.append((np.asarray(r.req.prompt, np.int64),
                        np.asarray(r.req.tokens(), np.int64), r.req))
    return out


def run_cell(args, root: Path = ROOT, require_tpu: bool = True,
             control: bool = False,
             mix_override: Optional[Dict] = None, on_run=None) -> Dict:
    """One whole run; returns the result object (printed by ``main``).
    ``control`` also reads the float8 control's gap (``control.py``);
    ``mix_override`` replaces keys of the mix and ``on_run`` receives
    the driver's record (``sweep.py``)."""
    import jax
    import check
    import peaks
    import trace_reduce
    cell = load_cell(args.workload, root)
    config, mix = cell["config"], {**cell["mix"], **(mix_override or {})}
    import_program(root)
    enable_cache(root)
    if require_tpu:
        device = check_device(cell["cell"]["chips"])
        pk = peaks.for_kind(device["kind"])
    else:
        d = jax.devices()[0]
        device = {"platform": d.platform, "kind": d.device_kind, "count": 1}
        pk = peaks.TPU_V5E
    model = model_of(cell)
    arch = model.arch_of(config)
    engine = build_engine(config, args.seed, model)
    warm = warm_up(engine, mix, config, args.seed)
    print(f"warm-up: decode widths {warm['widths']}, "
          f"{warm['prefill_groups']} prefill groups")
    requests, clients = plan(mix, config, args.seed, args.seconds,
                             arch["vocab_size"])
    loop = new_loop(engine, mix, config)
    driver = Driver(loop, mix, requests, clients)
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") \
        if args.trace else None
    trace_s = min(float(args.seconds), float(mix["trace_seconds"]))
    setup_s = time.perf_counter() - T_PROCESS
    try:
        run = driver.serve(float(mix["lead_in_s"]), float(args.seconds),
                           trace_s, trace_dir)
        stats_dev = jax.devices()[0].memory_stats() or {}
        peak = stats_dev.get("peak_bytes_in_use")
        late = run.late or [0.0]
        print(f"window: compiles={run.compiles_window} "
              f"lead_in_compiles={run.compiles_lead} "
              f"compiled_or_loaded_in_lead_in={run.compiled_in_lead} "
              f"compiled_or_loaded_in_window={run.compiled_in_window} "
              f"forwards={run.forwards_between(run.w0, run.w1)} "
              f"generator_late_max_s={max(late)!r} "
              f"generator_late_mean_s={sum(late) / len(late)!r}")
        print(f"memory: peak_bytes_in_use={peak}")
        if mix["loop"] == "open":
            # every request due in the window is followed to its end
            due = run.due_in_window()
            failed = [r for r in due if r.n < r.max_tokens]
            done = finished_requests(run, math.inf)
            attempted = len(due)
        else:
            # the window's work is its tokens: requests in flight at the
            # close are left there, not failed
            failed = []
            done = finished_requests(run, run.w1)
            attempted = len(done)
        reduced = None
        if trace_dir is not None:
            reduced = trace_reduce.reduce_file(
                trace_reduce.find_xplane(trace_dir))
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    if on_run is not None:
        on_run(run)
    # free the program's state before the reference runs
    del driver, loop, engine
    gc.collect()
    picks = check.sample(done, args.seed, int(mix["check_requests"]))
    chosen = [done[i] for i in picks]
    got = {}
    if chosen:
        got = model.gaps(arch, args.seed, chosen, control=control)
        print(f"check: {len(chosen)} requests, {got['positions']} served "
              f"tokens, max_logit_gap={got['max_logit_gap']!r} "
              f"mean_logit_gap={got['mean_logit_gap']!r}")
    checks = {name: {"value": got.get(name), "limit": float(limit)}
              for name, limit in config["correct"].items()}
    checks["unfinished_due_requests"] = {"value": len(failed), "limit": 0}
    correct = all(c["value"] is not None and c["value"] <= c["limit"]
                  for c in checks.values())
    result = {"correct": correct, "attempted": attempted,
              "failed": len(failed), "metrics": {}, "device": dict(device)}
    result["device"]["memory_peak_bytes"] = peak
    if args.trace:
        ctx = Context(run, arch, config, pk, reduced, model)
        for m in cell["per_layer"]:
            v = load_reader(m["name"])(ctx)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v,
                                                "unit": m["unit"]}
        result["device"]["busy_s"] = reduced.busy_s
        result["device"]["window_s"] = reduced.window_s
        result["breakdown"] = {
            "device_ops": trace_reduce.top_ops(reduced.ops),
            "idle_gaps": trace_reduce.top(reduced.idle)}
    else:
        e2e = end_to_end(run, float(args.seconds), setup_s)
        for m in cell["end_to_end"]:
            if m["name"] in e2e:
                result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                                "unit": m["unit"]}
    if control and chosen:
        result["readings"] = {k: got[k] for k in ("max_logit_gap",
                                                  "mean_logit_gap")}
        result["control"] = got["control"]
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args)
    except Refused as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
