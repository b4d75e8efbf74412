"""One run of one benchmark cell: set-up, the measured window, the check.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric is a file found by its name in ``BENCHMARK.json``:

    configs/<config>.json   engine, plane, flush span, keys, guarantee
    mixes/<traffic>.json    {"driver": <kind>, ...that kind's parameters}
    drivers/<kind>.py       run(ctx) -> the window's record
    limits/<workload>.json  the limit of each number the check compares
    metrics/<metric>.py     read(run) -> number or None

The program is used only through ``repro.engine``: ``EngineConfig``,
``SketchEngine(cfg, plane=, flush_elems=, plane_opts=)`` and its
``ingest``, ``flush``, ``state`` and ``sample_state``.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys
import time

import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))

import reference as ref  # noqa: E402
import tracing  # noqa: E402
import work  # noqa: E402
import ycsb  # noqa: E402


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# -- discovery -----------------------------------------------------------------

def find(kind: str, name: str, dirs, ext: str) -> str:
    for d in dirs:
        path = os.path.join(d, kind, name + ext)
        if os.path.isfile(path):
            return path
    raise FileNotFoundError(f"no {kind}/{name}{ext} under {list(dirs)}")


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    name = "bench_" + os.path.basename(path)[:-3].replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """A workload of ``BENCHMARK.json`` with its files loaded."""

    def __init__(self, bench: dict, workload: str, dirs):
        cells = {w["name"]: w for w in bench["workloads"]}
        if workload not in cells:
            raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
        w = cells[workload]
        self.name = workload
        self.chips = int(w["chips"])
        self.config = load_json(find("configs", w["config"], dirs, ".json"))
        self.mix = load_json(find("mixes", w["traffic"], dirs, ".json"))
        self.limits = load_json(find("limits", workload, dirs, ".json"))
        self.driver = load_module(find("drivers", self.mix["driver"], dirs,
                                       ".py"))

        def mine(m):
            return workload in m.get("workloads", [workload])

        self.end_to_end = [m for m in bench["end_to_end"] if mine(m)]
        reported = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in bench["per_layer"]
                          if m["moves"] in reported and mine(m)]
        self.metric_readers = {
            m["name"]: load_module(find("metrics", m["name"], dirs, ".py"))
            for m in self.per_layer}


# -- the chip ------------------------------------------------------------------

def device_info(chips: int, require_tpu: bool) -> dict:
    import jax

    devs = jax.devices()
    if require_tpu:
        if jax.default_backend() != "tpu":
            raise NoChip(f"JAX backend is {jax.default_backend()!r}, not tpu")
        if len(devs) < chips:
            raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def enable_cache() -> None:
    """The program's persistent compilation cache (``.jax_cache/`` in the
    checkout unless ``JAX_COMPILATION_CACHE_DIR`` names another), holding
    every program, however small or quick to compile."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Counts JAX compile events (tracing, lowering, backend compile)."""

    def __init__(self):
        import jax

        self.events = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            self.events += 1

    def close(self) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on)


# -- the system under test -----------------------------------------------------

def engine_seed(seed: int) -> int:
    return int(np.random.SeedSequence([int(seed), 7]).generate_state(1)[0])


def make_engine(config: dict, seed: int):
    from repro import engine as E

    cfg = E.EngineConfig(**config["engine"], seed=engine_seed(seed))
    return E.SketchEngine(cfg, plane=config["plane"],
                          flush_elems=config["flush_elems"],
                          plane_opts=config.get("plane_opts") or None)


class Context:
    """What a driver gets: the engine, the pool and the run's instruments.

    ``ingest(i)`` ingests pool block ``i % len(pool)`` and records it;
    ``read(stream)`` drains and samples (one stream of the state, or all
    of it where ``stream`` is None) and keeps the result for the check;
    ``settle()`` waits for every dispatch made so far.
    """

    def __init__(self, cell: Cell, seed: int, seconds: float, spans):
        import jax
        import jax.numpy as jnp

        self.cell, self.seed, self.seconds, self.spans = cell, seed, seconds, spans
        self.sync_reads = spans.annotate
        c = cell.config
        self.k = int(c["sample_k"])
        # A mix with a ``pool_seed`` replays the same blocks for every run
        # seed, from a block the seed picks, so that every seed does the
        # same work; without one the blocks are drawn from the run's seed.
        blocks = int(cell.mix["pool_blocks"])
        with spans.span("generate"):
            self.pool, self.primer = ycsb.build_pool(
                int(cell.mix.get("pool_seed", seed)),
                c["engine"]["num_streams"], c["flush_elems"], blocks,
                c["keys"]["records"], c["keys"]["theta"], c["keys"]["retract"],
                start=int(ycsb.rng_for(seed, 4).integers(blocks)))
        self.live = [int((k >= 0).sum()) for k, _ in self.pool]
        self.engine = make_engine(c, seed)
        self.sequence: list = []     # pool indices ingested (the primer's
        #                              is len(pool)), in order
        self.reads: list = []        # (stream, blocks ingested before, keys,
        #                              transformed, threshold, candidates)
        self.work = {"scatter_bytes": 0, "query_bytes": 0}
        self._tick = jax.jit(lambda x: x + 1)
        self._token = jnp.zeros((), jnp.int32)
        self._take = jax.jit(lambda st, i: jax.tree_util.tree_map(
            lambda x: jax.lax.dynamic_slice_in_dim(x, i, 1), st))
        self.inflight: list = []

    # -- operations --
    def tick(self):
        """A tiny program enqueued after everything dispatched so far; the
        chip runs one program at a time, in order, so it is done only
        when they are."""
        self._token = self._tick(self._token)
        return self._token

    def ingest(self, i: int) -> int:
        """Ingest pool block ``i`` (cyclically); ``i = -1`` is the primer."""
        b = len(self.pool) if i < 0 else i % len(self.pool)
        keys, vals = self.pool[b] if b < len(self.pool) else self.primer
        live = int((keys >= 0).sum()) if b == len(self.pool) else self.live[b]
        with self.spans.span("ingest"):
            self.engine.ingest(keys, vals)
        self.sequence.append(b)
        c = self.cell.config
        streams = c["engine"]["num_streams"] * int(
            (c.get("plane_opts") or {}).get("shards", 1))
        self.work["scatter_bytes"] += work.scatter_bytes(
            live, streams, c["engine"]["rows"], c["engine"]["width"])
        self.work["query_bytes"] += work.query_bytes(
            streams * c["engine"]["candidates"] + live, c["engine"]["rows"])
        return live

    def bound_inflight(self, depth: int) -> None:
        """Wait until at most ``depth`` ingests are in flight."""
        self.inflight.append(self.tick())
        while len(self.inflight) > depth:
            with self.spans.span("wait"):
                self.inflight.pop(0).block_until_ready()

    def settle(self) -> None:
        with self.spans.span("wait"):
            self.engine.flush()
            self.tick().block_until_ready()
            self.inflight.clear()

    def read(self, stream):
        import jax

        sp = self.spans
        with sp.span("read"):
            with sp.span("read.flush"):
                self.engine.flush()
                if self.sync_reads:
                    self.tick().block_until_ready()
            with sp.span("read.state"):
                st = self.engine.state
                if self.sync_reads:
                    jax.block_until_ready(st)
            if stream is not None:
                st = self._take(st, np.int32(stream))
            with sp.span("read.query"):
                s = self.engine.sample_state(st, self.k)
                out = (np.asarray(s.keys)[0], np.asarray(s.transformed)[0],
                       float(np.asarray(s.threshold)[0]))
        # the candidates stay on the device until the check
        self.reads.append((stream, len(self.sequence)) + out + (st.cand_keys,))
        return out

    # -- set-up --
    def warm(self) -> None:
        """Compile every program the window will run, with the primer
        ingested first so that block 0's retractions cancel insertions.
        A plane that routes blocks into shards of varying width is warmed
        on a second engine with every pool block."""
        reads = self.cell.mix.get("reads_per_s", 0) > 0
        if self.cell.config["plane"] == "pipeline":
            spare = make_engine(self.cell.config, self.seed)
            for keys, vals in self.pool:
                spare.ingest(keys, vals)
            spare.flush()
            if reads:
                spare.sample_state(spare.state, self.k)
            import jax

            jax.block_until_ready(spare.state)
            del spare
        self.ingest(-1)
        self.settle()
        if reads:
            self.read(0 if self.cell.mix.get("read") == "one_stream" else None)
            self.reads.clear()
        self.work = {"scatter_bytes": 0, "query_bytes": 0}


# -- the check -----------------------------------------------------------------

def check(ctx: Context, checked: int = 16) -> dict:
    """Compare the timed path's state and reads with the plain reference.

    Numbers (each the worst over what is checked):
      table_err  max |table - reference| / max |reference| per stream
      cand_err   ranked gap, over the first k/16 ranks, of the estimates of
                 the final candidates on the program's table against those
                 of the reference's one-pass policy (``ref.OnePass``) on
                 the reference table, / max |reference|: the candidate
                 refresh's query and top-C
      topk_miss  share of the top-k keys of every key's reference estimate
                 missing from the final candidates (no reads) or from a
                 read's sample
      est_err    max |read's transformed - reference estimate| / max |ref|
      thr_err    |read's threshold - the (k+1)-st largest reference
                 estimate among that read's candidates| / the latter

    Reported, not compared: ``topk_miss`` (the one-pass policy keeps C
    candidates, so a key it evicted can later grow, by collisions, above
    keys it kept: a sound run misses a few percent of the top-k, and now
    and then a key of the first k/16 ranks), ``table_err_median`` (over the
    checked streams) and ``table_err_cell`` (the worst cell's error over
    the magnitude summed into it, which separates the rounding of the sums
    from errors of single transformed events).
    """
    import jax

    c = ctx.cell.config
    eng_c = c["engine"]
    rows, width, p, scheme = (eng_c["rows"], eng_c["width"], float(eng_c["p"]),
                              eng_c.get("scheme", "ppswor"))
    B = eng_c["num_streams"]
    rng = ycsb.rng_for(ctx.seed, 3)
    streams = sorted(rng.choice(B, size=min(B, checked), replace=False).tolist())

    st = ctx.engine.state
    seeds = np.asarray(st.sketch.seed).reshape(-1)
    tseeds = np.asarray(st.seed_transform).reshape(-1)
    idx = jax.numpy.asarray(np.asarray(streams, np.int32))
    tables = np.asarray(st.sketch.table[idx], np.float64)
    cands = np.asarray(st.cand_keys[idx])
    del st

    counts = {}

    def counts_of(b):
        if b not in counts:
            blocks = ctx.pool + [ctx.primer]
            counts[b] = ref.StreamCounts([k[b] for k, _ in blocks],
                                         [v[b] for _, v in blocks])
        return counts[b]

    def mult(n):
        m = np.zeros(len(ctx.pool) + 1, np.int64)
        for b in ctx.sequence[:n]:
            m[b] += 1
        return m

    def reference_at(b, n):
        sc = counts_of(b)
        tab = ref.table(sc.keys, sc.freqs(mult(n)), seeds[b], tseeds[b],
                        rows, width, p, scheme)
        est = np.abs(ref.estimate(tab, sc.keys, seeds[b]))
        order = np.argsort(-est, kind="stable")
        return tab, sc.keys[order[:ctx.k]], est[order]

    heavy = max(ctx.k // 16, 1)
    shards = int((c.get("plane_opts") or {}).get("shards", 1))

    def policy_cands(b, n):
        """The reference's candidates of stream ``b`` after ``n`` blocks."""
        pol = ref.OnePass(seeds[b], tseeds[b], rows, width, p, scheme,
                          eng_c["candidates"], shards)
        blocks = ctx.pool + [ctx.primer]
        for i in ctx.sequence[:n]:
            pol.flush(blocks[i][0][b], blocks[i][1][b])
        return pol.collapse()

    def ranked(tab, keys, seed, n=heavy):
        """|reference estimate| of ``keys[:n]`` against ``tab``; an empty
        slot reads 0."""
        keys = np.asarray(keys)[:n]
        out = np.zeros(n)
        live = keys >= 0
        out[:keys.size][live] = np.abs(ref.estimate(tab, keys[live], seed))
        return out

    out = {"table_err": 0.0, "cand_err": 0.0, "topk_miss": 0.0}
    n_all = len(ctx.sequence)
    per_stream, per_cell = [], []
    for j, b in enumerate(streams):
        tab, top, _ = reference_at(b, n_all)
        scale = max(float(np.abs(tab).max()), 1.0)
        diff = np.abs(tables[j] - tab)
        sc = counts_of(b)
        m = ref.mass(sc.keys, sc.events(mult(n_all)), seeds[b], tseeds[b],
                     rows, width, p, scheme)
        per_stream.append(float(diff.max()) / scale)
        per_cell.append(float((diff / np.maximum(m, 1e-30))[m > 0].max(
            initial=0.0)))
        out["table_err"] = max(out["table_err"], per_stream[-1])
        # cand_err: over the first k/16 ranks, the widest gap between the
        # i-th largest estimate among the program's candidates, read from
        # the program's table, and the i-th largest reference estimate
        # among the reference policy's candidates.  Rounding costs the
        # error of a heavy estimate; a near-tie swapped, nothing more; a
        # wrong bucket, a heavy key dropped or candidates left unrefreshed
        # cost a heavy key's estimate.
        C = eng_c["candidates"]
        want = np.sort(ranked(tab, policy_cands(b, n_all), seeds[b], C)
                       )[::-1][:heavy]
        mine = np.sort(ranked(tables[j], cands[j], seeds[b], C))[::-1][:heavy]
        gap = np.abs(mine - want).max()
        out["cand_err"] = max(out["cand_err"], float(gap) / scale)
        if not ctx.reads:
            miss = np.setdiff1d(top, cands[j]).size / ctx.k
            out["topk_miss"] = max(out["topk_miss"], miss)
    out["table_err_median"] = float(np.median(per_stream))
    out["table_err_cell"] = max(per_cell)
    pick = []
    if ctx.reads:
        out["est_err"] = out["thr_err"] = 0.0
        pick = sorted(set(rng.choice(len(ctx.reads), size=min(
            len(ctx.reads), checked), replace=False).tolist())
            | {len(ctx.reads) - 1})
        for r in pick:
            stream, n, keys, tv, thr, cand = ctx.reads[r]
            b = 0 if stream is None else stream
            tab, top, _ = reference_at(b, n)
            cand = np.asarray(cand).reshape(-1)
            among = np.sort(np.abs(ref.estimate(
                tab, cand[cand >= 0], seeds[b])))[::-1]
            scale = max(float(np.abs(tab).max()), 1.0)
            live = keys >= 0
            est = ref.estimate(tab, keys[live], seeds[b])
            out["est_err"] = max(out["est_err"], float(
                np.abs(tv[live] - est).max(initial=0.0)) / scale)
            if among.size > ctx.k:
                kth = among[ctx.k]
                thr_err = abs(thr - kth) / max(kth, 1e-30)
            else:        # k or fewer candidates: no (k+1)-st, thr < 0
                thr_err = 0.0 if thr < 0 else 1.0
            out["thr_err"] = max(out["thr_err"], thr_err)
            miss = np.setdiff1d(top, keys).size / ctx.k
            out["topk_miss"] = max(out["topk_miss"], miss)
    out["reads_checked"] = len(pick)
    out["streams_checked"] = len(streams)
    return out


# -- one run -------------------------------------------------------------------

def quantile(xs, q: float) -> float:
    return float(np.percentile(np.asarray(xs, np.float64), q))


def run(bench_path: str, workload: str, seed: int, seconds: float,
        trace: bool, dirs=(BENCH_DIR,), require_tpu: bool = True,
        t_start: float | None = None, log=print, mix: dict | None = None,
        keep_trace: str | None = None) -> dict:
    """One run; returns the result object (the contract's last line).
    ``mix`` overrides parameters of the cell's traffic mix (the sweep);
    ``keep_trace`` is a path to copy the profiler's trace to."""
    t_start = time.perf_counter() if t_start is None else t_start
    bench = load_json(bench_path)
    cell = Cell(bench, workload, dirs)
    cell.mix.update(mix or {})
    import jax

    device = device_info(cell.chips, require_tpu)
    if require_tpu:
        enable_cache()
    compiles = CompileCounter()
    spans = tracing.Spans(annotate=trace)
    ctx = Context(cell, seed, seconds, spans)
    ctx.warm()
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f} s, pool {len(ctx.pool)} blocks of "
        f"{ctx.pool[0][0].shape}, {sum(ctx.live)} live events")

    trace_dir = None
    if trace:
        import tempfile

        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # host spans come from TraceAnnotation
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    c0 = compiles.events
    with spans.span(tracing.WINDOW):
        rec = cell.driver.run(ctx, cell.mix)
    window_compiles = compiles.events - c0
    compiles.close()
    summary = None
    if trace:
        jax.profiler.stop_trace()
        import glob
        import shutil

        pb = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                       recursive=True)
        summary = tracing.reduce(tracing.load(pb[0]))
        if keep_trace:
            shutil.copyfile(pb[0], keep_trace)
        shutil.rmtree(trace_dir, ignore_errors=True)
    stats = jax.devices()[0].memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))

    lat = rec.get("read_latency_s", [])
    values = {
        "setup_s": setup_s,
        "ingest_events_per_s": rec["events"] / rec["elapsed_s"],
    }
    if lat:
        values["read_p95_ms"] = 1e3 * quantile(lat, 95)
        values["read_p50_ms"] = 1e3 * quantile(lat, 50)
    log(f"window {rec['elapsed_s']:.3f} s: {rec['events']} events, "
        f"{len(ctx.sequence)} blocks ingested in all, {len(lat)} reads, "
        f"compiles inside the window {window_compiles}, ingest lateness "
        f"first/last quarter {rec.get('lateness_s')}")

    run_rec = {"spans": spans, "trace": summary, "events": rec["events"],
               "work": ctx.work, "peak": None, "record": rec}
    if summary is not None:
        run_rec["peak"] = work.peaks(device["kind"]) if require_tpu else None
    if trace:
        metrics = {}
        for m in cell.per_layer:
            v = cell.metric_readers[m["name"]].read(run_rec)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] in values}

    got = check(ctx)
    checks = {k: {"value": got[k], "limit": cell.limits[k]}
              for k in sorted(cell.limits)}
    correct = all(v["value"] <= v["limit"] for v in checks.values()) \
        and rec["failed"] == 0
    device = dict(device, memory_peak_bytes=peak)
    if summary is not None:
        device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
    result = {"correct": bool(correct), "attempted": rec["attempted"],
              "failed": rec["failed"], "metrics": metrics, "device": device}
    if summary is not None:
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    result["window_compiles"] = window_compiles
    result["lateness_s"] = rec.get("lateness_s")
    result["info"] = {k: v for k, v in got.items() if k not in checks}
    result["checks"] = checks
    return result


def print_checks(result: dict, file=sys.stderr) -> None:
    for name, v in result["checks"].items():
        print(f"check {name} = {v['value']!r} limit {v['limit']!r}", file=file)
