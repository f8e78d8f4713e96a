"""One run of one cell: set-up, the measured window, the check.

``run_cell`` is what ``run.py`` calls.  The phases:

1. Set-up (``setup_s``, from process start to window start): read the cell,
   its configuration and its traffic mix from ``BENCHMARK.json`` and their
   files; make the weights on the device from ``--seed``; build the engine
   through the program's own entry, ``launch.serve.build_engine``; compile
   every width plan (``warm_compile``); cross the first plan boundary on an
   idle engine; serve one request per distinct prompt length of the run's
   schedule (a seed stream apart from the window's), so that every shape
   the window meets is compiled; then the mix's preroll, so the window
   opens in steady state.
2. The window: ``--seconds`` on the host clock.  Each request is handed to
   the engine when it falls due and timed from then.  After each
   ``step()`` (which ends on a host sync) the tokens each request holds are
   read and stamped with the host clock.
3. After the window: the requests that fell due in it are served out
   (open loop, at most ``DRAIN_CAP_S``); the peak device memory is read;
   the engine is freed; then the plain reference, run over a sample of the
   finished requests, decides ``correct``.

Nothing of this module runs inside the measured loop except the request
bookkeeping in ``_serve``.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
CLOCK = time.monotonic

DRAIN_CAP_S = 60.0         # serve-out after the window, open loop
SAMPLE_TOKENS = 512        # the check covers at least this many served
SAMPLE_MIN, SAMPLE_MAX = 4, 24   # tokens, over this many requests
TRACE_MAX_S = 60.0         # a traced run traces at most this much window

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# ModelConfig field <- configuration-file key
FIELDS = {
    "n_layers": "num_hidden_layers", "d_model": "hidden_size",
    "n_heads": "num_attention_heads", "n_kv_heads": "num_key_value_heads",
    "d_ff": "intermediate_size", "vocab_size": "vocab_size",
    "rope_theta": "rope_theta", "tie_embeddings": "tie_word_embeddings",
    "qkv_bias": "attention_bias", "head_dim": "head_dim",
    "window": "sliding_window", "n_experts": "num_experts",
    "experts_per_token": "num_experts_per_tok",
    "moe_d_ff": "moe_intermediate_size",
}

# A reduced size for the CPU rehearsal (``--rehearse``) and the tests:
# published head size 64, a d_ff the planner can cut.  A configuration
# file's own ``"rehearsal"`` object is merged over ``conf`` (its
# ``"program"`` and ``"engine"`` objects key by key), so a model with
# experts or windows sets their reduced sizes in its own file.
REHEARSAL = {
    "conf": {"num_hidden_layers": 2, "hidden_size": 256,
             "num_attention_heads": 4, "num_key_value_heads": 4,
             "head_dim": 64, "intermediate_size": 640, "vocab_size": 512},
    "engine": {"slots": 4, "max_len": 256, "prefill_chunk": 64,
               "prefill_bucket_min": 32},
    "scale_len": 1.0 / 8.0,
    "scale_preroll": 1.0 / 4.0,
}


class NoChip(RuntimeError):
    """The run needs an accelerator JAX did not find."""


def process_age() -> float:
    """Seconds since this process started (Linux), else since import."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        boot = time.clock_gettime(time.CLOCK_BOOTTIME)
        return boot - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError, AttributeError):
        return CLOCK() - _IMPORTED


_IMPORTED = CLOCK()


# ---------------------------------------------------------------------------
# the cell, its configuration and its mix
# ---------------------------------------------------------------------------
def load_benchmark() -> dict:
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def cell_spec(bench: dict, name: str) -> tuple:
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    confs = {c["name"]: c for c in bench["configs"]}
    entry = confs[cell["config"]]
    with open(ROOT / entry["file"]) as f:
        conf = json.load(f)
    from traffic import load_mix
    return cell, conf, load_mix(cell["traffic"])


def reference_module(conf: dict):
    """The plain reference the configuration file names."""
    return importlib.import_module(f"references.{conf['reference']}")


def program_config(conf: dict, ref_mod=None):
    """The program's ModelConfig serving exactly what the file states:
    the published keys through ``FIELDS``, then the file's ``"program"``
    object, ModelConfig fields set verbatim (a layer pattern, MoE).  The
    reference (``ref_mod``, else the one the file names) says whether it
    computes that model: its ``accepts(cfg, conf)`` raises if not."""
    import dataclasses as dc
    from repro.configs import get_config
    base = get_config(conf["program_arch"])
    over = {f: conf[k] for f, k in FIELDS.items() if k in conf}
    known = {f.name for f in dc.fields(base)}
    for f, v in conf.get("program", {}).items():
        if f not in known:
            raise KeyError(f"program field {f!r} is not a ModelConfig "
                           f"field")
        over[f] = tuple(v) if isinstance(v, list) else v
    cfg = dc.replace(base, **over)
    if dc.replace(cfg, head_dim=0).head_dim != cfg.head_dim \
            and "head_dim" not in conf:
        raise ValueError("head_dim differs from hidden_size / heads")
    (ref_mod or reference_module(conf)).accepts(cfg, conf)
    return cfg


def check_layer_kinds(cfg, model) -> None:
    """Raise unless the work counts (``model``, a ``work.ModelShape`` read
    from the published keys) see each layer as the program runs it."""
    from repro.models.transformer import layer_plan
    plan = layer_plan(cfg)
    attn = {"attn": "full_attention", "local": "sliding_attention"}
    mlp = {"dense": "dense", "moe": "sparse"}
    runs = ([attn.get(a, a) for a, _ in plan],
            [mlp.get(f, f) for _, f in plan])
    counted = (list(model.layer_types) or ["full_attention"] * len(plan),
               list(model.mlp_layer_types) or ["dense"] * len(plan))
    if runs != counted:
        raise ValueError(f"the program runs layers {runs}; the work counts "
                         f"read {counted}")
    if "local" in cfg.block_pattern and cfg.window != model.sliding_window:
        raise ValueError(f"window {cfg.window} run, {model.sliding_window} "
                         f"counted")
    # The program's shared expert runs at the dense width, ``cfg.d_ff``.
    runs = (cfg.n_experts, cfg.experts_per_token, cfg.moe_d_ff,
            cfg.d_ff if cfg.shared_expert else 0)
    counted = (model.n_experts, model.experts_per_token, model.expert_ffn,
               model.shared_ffn)
    if cfg.moe and runs != counted:
        raise ValueError(f"experts, per token, width, shared width: {runs} "
                         f"run, {counted} counted")


def rehearsal_conf(conf: dict) -> dict:
    own = conf.get("rehearsal", {})
    c = dict(conf, **REHEARSAL["conf"])
    c.update({k: v for k, v in own.items()
              if k not in ("engine", "program")})
    c["engine"] = {**conf["engine"], **REHEARSAL["engine"],
                   **own.get("engine", {})}
    if "program" in own:
        c["program"] = {**conf.get("program", {}), **own["program"]}
    return c


# ---------------------------------------------------------------------------
# run records
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class ReqRecord:
    planned: object
    rid: int
    due: float                      # host clock
    token_t: List[float] = dataclasses.field(default_factory=list)
    seated_t: Optional[float] = None
    ok: bool = False
    done: bool = False


@dataclasses.dataclass
class RunRecord:
    """What the per-layer metric readers (``metrics/*.py``) read."""

    mode: str
    window: tuple                   # host clock (start, end)
    requests: List[ReqRecord]
    steps: List[tuple]              # host clock (start, end) of each step
    calls: list                     # engine_adapter.Call, in the window
    compiles: List[tuple]           # (host clock, seconds)
    model: object                   # work.ModelShape
    peaks: dict
    exec_modules: set = dataclasses.field(default_factory=set)
    trace: object = None            # trace_reduce.Reduced or None
    clock_to_trace: float = 0.0     # add to host clock -> trace seconds

    def in_window(self, t: float) -> bool:
        return self.window[0] <= t < self.window[1]

    def window_requests(self) -> List[ReqRecord]:
        return [r for r in self.requests if self.in_window(r.due)]


class _Compiles:
    """Backend compiles (or loads from the persistent cache) as JAX's
    monitoring reports them; one listener per process."""

    events: List[tuple] = []
    _listening = False

    def __init__(self):
        if not _Compiles._listening:
            import jax.monitoring
            jax.monitoring.register_event_duration_secs_listener(
                _Compiles._on)
            _Compiles._listening = True

    @staticmethod
    def _on(event, secs, **kw):
        if event == BACKEND_COMPILE_EVENT:
            _Compiles.events.append((CLOCK(), float(secs)))


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------
def add_paths() -> None:
    """The program (``src``) and the benchmark's own modules."""
    for p in (str(ROOT / "src"), str(HERE)):
        if p not in sys.path:
            sys.path.insert(0, p)


def init_jax():
    """JAX with its persistent compilation cache in the checkout, at a
    fixed path, and every executable cached however small.  Call before
    anything imports jax."""
    cache = ROOT / ".jax_cache"
    cache.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    add_paths()
    import jax
    jax.config.update("jax_compilation_cache_dir", str(cache))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


class Session:
    """The engine built and warmed for one cell and one seed."""

    def __init__(self, cell_name: str, seed: int, seconds: float, *,
                 rehearse: bool = False, log=print, rates=()):
        import jax
        from engine_adapter import EngineAdapter
        from traffic import build_schedule, warmup_prompts
        from weights import make_weights
        from work import ModelShape
        from peaks import peaks_for

        self.log = log
        self.rehearse = rehearse
        self.bench = load_benchmark()
        self.cell, conf, self.mix = cell_spec(self.bench, cell_name)
        self.conf = rehearsal_conf(conf) if rehearse else conf
        chips = int(self.cell["chips"])
        devs = jax.devices()
        self.device = devs[0]
        if not rehearse and (self.device.platform != "tpu"
                             or len(devs) < chips):
            raise NoChip(f"the cell needs {chips} TPU chip(s); JAX found "
                         f"{len(devs)} {self.device.platform} device(s)")
        self.chips = chips
        self.peaks = peaks_for("TPU v5 lite" if rehearse
                               else self.device.device_kind)
        self.compiles = _Compiles()

        from repro.core.hardware import TPU_V5E, hardware_for_kind
        from repro.launch.serve import build_engine
        from repro.models import init_params

        self.ref_mod = reference_module(self.conf)
        self.cfg = program_config(self.conf, self.ref_mod)
        self.model = ModelShape.from_conf(self.conf)
        check_layer_kinds(self.cfg, self.model)
        e = self.conf["engine"]
        self.engine_shape = e
        scale = REHEARSAL["scale_len"] if rehearse else 1.0
        if rehearse:
            self.mix = dict(self.mix, preroll_s=self.mix.get("preroll_s", 0)
                            * REHEARSAL["scale_preroll"])
        self.schedule = build_schedule(self.mix, seed, seconds,
                                       self.cfg.vocab_size, e["slots"],
                                       scale_len=scale)
        # other offered rates of the same mix (the knee sweep)
        self.at_rate = {r: build_schedule(dict(self.mix, rate_rps=r), seed,
                                          seconds, self.cfg.vocab_size,
                                          e["slots"], scale_len=scale)
                        for r in rates}
        hw = TPU_V5E if rehearse else hardware_for_kind(
            self.device.device_kind)
        self.weights = make_weights(self.cfg, seed, init_params,
                                    self.ref_mod)
        self.engine, plans = build_engine(
            self.weights, self.cfg, hw, slots=e["slots"],
            max_len=e["max_len"], prefill_chunk=e["prefill_chunk"],
            prefill_bucket_min=e["prefill_bucket_min"],
            boundary_every=e["boundary_every"])
        self.ad = EngineAdapter(self.engine, CLOCK)
        lengths = sorted(set(self.schedule.prompt_lengths()).union(
            *(sc.prompt_lengths() for sc in self.at_rate.values())))
        t = CLOCK()
        n = self.engine.warm_compile(plans, prefill_lengths=lengths)
        log(f"warm_compile: {n} executables in {CLOCK() - t:.2f}s")
        self.crossings_expected = 0 if self.ad.plan_is_full(plans) else 1
        if self.crossings_expected:
            for _ in range(2 * e["boundary_every"]):
                if any(b.outcome == "ok" for b in self.ad.boundaries()):
                    break
                self.ad.step()
            crossed = [b for b in self.ad.boundaries() if b.outcome == "ok"]
            if not crossed:
                raise RuntimeError(f"no plan boundary crossed: "
                                   f"{self.ad.boundaries()}")
        self.widths = self.ad.widths()
        log(f"serving plan: heads {sorted(set(self.widths[0]))}, ffn "
            f"{sorted(set(self.widths[1]))} per layer")
        t, n0 = CLOCK(), len(self.compiles.events)
        prompts = warmup_prompts(lengths, self.cfg.vocab_size, seed)
        rids = [self.ad.submit(p, 2, CLOCK()) for p in prompts]
        while self.ad.outstanding():
            self.ad.step()
        bad = [r for r, p in self.ad.progress(rids).items() if not p.ok]
        if bad:
            raise RuntimeError(f"{len(bad)} warm-up requests failed")
        ev = self.compiles.events[n0:]
        log(f"warm-up: {len(prompts)} prompt lengths served in "
            f"{CLOCK() - t:.2f}s; {len(ev)} executables compiled or "
            f"loaded in {sum(e[1] for e in ev):.2f}s")

    def exec_modules(self) -> set:
        """HLO module names of the engine's serving executables, as the
        trace names their executions."""
        cache = self.engine.compile_cache
        names = set()
        for ev in cache.events:
            if ev.outcome == "compiled":
                exe = cache.executable(ev.kind, ev.key[2], ev.key[3])
                if exe is not None:
                    head = exe.as_text().split("\n", 1)[0].split()
                    if len(head) > 1 and head[0] == "HloModule":
                        names.add(head[1].rstrip(","))
        return names

    def free_engine(self) -> None:
        self.ad = None
        self.engine = None
        gc.collect()


# ---------------------------------------------------------------------------
# the measured loop
# ---------------------------------------------------------------------------
def _serve(sess: Session, disp, recs: Dict[int, ReqRecord], live: set,
           t_end: float, steps: Optional[list], submit: bool,
           until_done: Optional[set] = None) -> None:
    """Step the engine until ``t_end``: hand out due requests (if
    ``submit``), step, stamp new tokens.  With ``until_done``, stop early
    once every rid in it is terminal."""
    ad = sess.ad
    while True:
        now = CLOCK()
        if submit:
            for p in disp.due(now):
                due = disp.t0 + p.due
                rid = ad.submit(p.tokens, p.max_new, due)
                recs[rid] = ReqRecord(planned=p, rid=rid, due=due)
                live.add(rid)
        if now >= t_end:
            return
        if until_done is not None and not (until_done & live):
            return
        if ad.outstanding():
            t0 = CLOCK()
            ad.step()
            t1 = CLOCK()
            if steps is not None:
                steps.append((t0, t1))
                ad.waiting_log.append((t1, ad.waiting()))
            for rid, pr in ad.progress(live).items():
                r = recs[rid]
                if pr.tokens > len(r.token_t):
                    r.token_t.extend([t1] * (pr.tokens - len(r.token_t)))
                if pr.seated_t is not None and r.seated_t is None:
                    r.seated_t = pr.seated_t
                if pr.done:
                    r.done, r.ok = True, pr.ok
                    live.discard(rid)
                    if submit and r.planned.client >= 0:
                        disp.finished(r.planned, t1)
        else:
            nxt = disp.next_due() if submit else None
            wake = t_end if nxt is None else min(nxt, t_end)
            time.sleep(max(0.0, min(wake - CLOCK(), 0.05)))


def percentile(x, q: float) -> Optional[float]:
    x = np.asarray(x, np.float64)
    return float(np.percentile(x, q)) if x.size else None


def end_to_end(run: RunRecord, seconds: float) -> Dict[str, float]:
    """Every end-to-end quantity the harness can compute; ``run_cell``
    reports those the cell names."""
    w0, w1 = run.window
    toks = [t for r in run.requests for t in r.token_t if w0 <= t < w1]
    gaps = [b - a for r in run.requests
            for a, b in zip(r.token_t, r.token_t[1:]) if w0 <= a and b < w1]
    out = {"output_tok_s": len(toks) / seconds}
    itl = percentile(gaps, 95)
    if itl is not None:
        out["itl_p95_ms"] = itl * 1e3
    if run.mode == "open":
        due = run.window_requests()
        ttft = [(r.token_t[0] if r.token_t else w1 + DRAIN_CAP_S) - r.due
                for r in due]
        if ttft:
            out["ttft_p50_ms"] = percentile(ttft, 50) * 1e3
            out["ttft_p95_ms"] = percentile(ttft, 95) * 1e3
    return out


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------
def check(sess: Session, recs: Dict[int, ReqRecord], served: dict,
          seed: int, limit: float, control: bool = False) -> dict:
    """The plain reference over a sample of the finished requests, drawn
    from the seed, with the longest in it.  Returns the widest gap by
    which a served token's logit lies below the reference's best (and,
    with ``control``, the same read for the fp8 control)."""
    done = [r for r in recs.values() if r.ok and r.rid in served
            and len(served[r.rid])]
    out = {"sampled_requests": 0, "sampled_tokens": 0,
           "logit_gap_max": None}
    if not done:
        return out
    rng = np.random.default_rng([int(seed), 3])
    longest = max(done, key=lambda r: (len(served[r.rid]),
                                       r.planned.prompt_len, -r.rid))
    rest = [r for r in done if r is not longest]
    order = [longest] + [rest[i] for i in rng.permutation(len(rest))]
    sample, n_tok = [], 0
    for r in order:
        if len(sample) >= SAMPLE_MAX or (len(sample) >= SAMPLE_MIN
                                         and n_tok >= SAMPLE_TOKENS):
            break
        sample.append(r)
        n_tok += len(served[r.rid])
    ref = sess.ref_mod.Reference(sess.conf, sess.widths[0], sess.widths[1],
                                 sess.engine_shape["max_len"])
    worst, worst_ctl = 0.0, 0.0
    for r in sample:
        g = ref.served_gaps(sess.weights, r.planned.tokens, served[r.rid])
        worst = max(worst, float(g.max()))
        if control:
            c = ref.control_gaps(sess.weights, r.planned.tokens,
                                 served[r.rid])
            worst_ctl = max(worst_ctl, float(c.max()))
    out.update(sampled_requests=len(sample), sampled_tokens=n_tok,
               logit_gap_max=worst)
    if control:
        out["control_gap_max"] = worst_ctl
    return out


# ---------------------------------------------------------------------------
# per-layer metrics
# ---------------------------------------------------------------------------
def load_reader(name: str):
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(bench: dict, cell_name: str, kind: str) -> list:
    """The cell's end-to-end (``kind='end_to_end'``) or per-layer
    metrics."""
    e2e = {m["name"]: m for m in bench["end_to_end"]}

    def listed(m):
        return cell_name in m.get("workloads", [cell_name])

    def reports(m):
        if kind == "per_layer" and "workloads" not in m:
            return listed(e2e[m["moves"]])
        return listed(m)

    return [m for m in bench[kind] if reports(m)]


@dataclasses.dataclass
class Window:
    recs: Dict[int, ReqRecord]
    t_w: float
    t_end: float
    setup_s: float
    steps: list
    calls: list
    in_window: set
    waiting: list                   # (host clock, requests not yet seated)
    trace_dir: Optional[str] = None
    t_tr: tuple = (0.0, 0.0)


def window(sess: Session, schedule, seconds: float, trace: bool) -> Window:
    """The schedule's preroll, then the measured window, then (open loop)
    the serve-out of the requests that fell due in it."""
    import jax
    from traffic import Dispatcher
    recs: Dict[int, ReqRecord] = {}
    live: set = set()
    t_w = CLOCK() + schedule.preroll_s
    disp = Dispatcher(schedule, t_w)
    _serve(sess, disp, recs, live, t_w, None, True)
    setup_s = process_age()
    n_calls0 = len(sess.ad.calls)
    steps: list = []
    t_end = t_w + seconds
    trace_dir, t_tr = None, (0.0, 0.0)
    if trace:
        import tempfile
        trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # the engine spans suffice
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        # the window's span anchors the host clock on the trace's
        with jax.profiler.TraceAnnotation("bench.window"):
            t0 = CLOCK()
            _serve(sess, disp, recs, live, min(t_end, t0 + TRACE_MAX_S),
                   steps, True)
            jax.effects_barrier()
            t_tr = (t0, CLOCK())
        jax.profiler.stop_trace()
    _serve(sess, disp, recs, live, t_end, steps, True)
    waiting = list(sess.ad.waiting_log)
    in_window = {rid for rid, r in recs.items() if t_w <= r.due < t_end}
    if schedule.mode == "open":
        _serve(sess, disp, recs, live, CLOCK() + DRAIN_CAP_S, None, False,
               until_done=in_window)
    return Window(recs=recs, t_w=t_w, t_end=t_end, setup_s=setup_s,
                  steps=steps, calls=sess.ad.calls[n_calls0:],
                  in_window=in_window, waiting=waiting,
                  trace_dir=trace_dir, t_tr=t_tr)


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------
def run_cell(cell_name: str, seed: int, seconds: float, trace: bool, *,
             rehearse: bool = False, fault: Optional[str] = None,
             control: bool = False, log=print) -> dict:
    """One run.  Returns the result object ``run.py`` prints (with the
    end-to-end metrics without ``trace``, the per-layer ones with it)."""
    from trace_reduce import reduce_trace

    sess = Session(cell_name, seed, seconds, rehearse=rehearse, log=log)
    if fault is not None:
        sess.ad.break_path(fault)
    if trace:
        sess.ad.tap(spans=True)
    w = window(sess, sess.schedule, seconds, trace)
    setup_s, recs, t_w, t_end = w.setup_s, w.recs, w.t_w, w.t_end
    steps, calls, in_window = w.steps, w.calls, w.in_window
    served = {rid: sess.ad.served_tokens(rid)
              for rid, r in recs.items() if r.ok}
    stats = sess.device.memory_stats() or {}
    mem_peak = stats.get("peak_bytes_in_use")
    boundaries = sess.ad.boundaries()
    exec_modules = sess.exec_modules() if trace else set()
    sess.free_engine()

    run = RunRecord(mode=sess.schedule.mode,
                    window=(t_w, t_end), requests=list(recs.values()),
                    steps=steps, calls=calls,
                    compiles=[c for c in sess.compiles.events
                              if t_w <= c[0] < t_end],
                    model=sess.model, peaks=sess.peaks,
                    exec_modules=exec_modules)
    limit = float(sess.conf["limits"]["logit_gap"])
    chk = check(sess, recs, served, seed, limit, control=control)
    gap = chk["logit_gap_max"]
    crossings = sum(b.outcome == "ok" for b in boundaries)
    correct = (gap is not None and gap <= limit
               and chk["sampled_tokens"] > 0
               and crossings == sess.crossings_expected
               and len(boundaries) == crossings)
    due = [recs[rid] for rid in in_window]
    failed = sum(1 for r in due if not r.ok and (r.done or run.mode == "open"))
    device = {"platform": sess.device.platform,
              "kind": sess.device.device_kind, "count": sess.chips,
              "memory_peak_bytes": mem_peak}
    result = {"correct": bool(correct), "attempted": len(due),
              "failed": int(failed)}
    bench = sess.bench
    if trace:
        red = reduce_trace(w.trace_dir, "bench.window", w.t_tr[0],
                           window_s=w.t_tr[1] - w.t_tr[0])
        _rmtree(w.trace_dir)
        run.trace = red
        if red is not None:
            run.clock_to_trace = red.offset
            device["busy_s"] = red.busy_s
            device["window_s"] = red.window_s
        metrics = {}
        for m in cell_metrics(bench, cell_name, "per_layer"):
            v = load_reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["metrics"] = metrics
        if red is not None:
            result["breakdown"] = red.breakdown()
    else:
        e2e = end_to_end(run, seconds)
        e2e["setup_s"] = setup_s
        metrics = {}
        for m in cell_metrics(bench, cell_name, "end_to_end"):
            if m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
        result["metrics"] = metrics
    result["device"] = device
    result["info"] = {"check": chk, "setup_s": setup_s,
                      "requests": len(recs), "window_requests": len(due),
                      "compiles_in_window": len(run.compiles),
                      "compile_s_in_window": sum(c[1] for c in run.compiles),
                      "steps": len(steps), "calls": len(calls),
                      "boundaries": [b.outcome for b in boundaries],
                      "widths": [sorted(set(x)) for x in sess.widths]}
    result["checks"] = {
        "logit_gap_max": {"value": gap, "limit": limit},
        "sampled_tokens": {"value": chk["sampled_tokens"], "limit": 1},
        "plan_boundaries": {"value": len(boundaries),
                            "limit": sess.crossings_expected},
    }
    return result


def _rmtree(path) -> None:
    import shutil
    shutil.rmtree(path, ignore_errors=True)
