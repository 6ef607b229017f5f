"""Run one benchmark cell once and print its result as the last line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration and a traffic mix.
Set-up builds the serving engine from the configuration (packed weights
drawn from the seed, paged KV, fused paged attention, greedy decoding),
warms every program shape the mix can use, and submits the mix's set-up
requests. The window then offers the mix's requests through ``submit()``
and ``step()`` for ``--seconds``; no program may compile inside it.
``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics, read from a profiler trace of part of the window.
Afterwards the engine is freed and a sample of what it served is
compared with the plain reference (``bench/check.py``).

Exits 2, printing no result, where JAX finds no TPU, fewer chips than
the cell asks for, or a chip missing from ``bench/peaks.json``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import numpy as np  # noqa: E402

from bench import cell as cells  # noqa: E402
from bench import check  # noqa: E402
from bench import trace as tr  # noqa: E402
from bench.client import Client  # noqa: E402
from bench.record import Call, Run  # noqa: E402

TRACE_SECONDS = 10.0
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class NoChip(RuntimeError):
    """The machine lacks what the cell needs: no result is printed."""


def log(msg: str) -> None:
    print(f"[bench {time.perf_counter() - T_START:8.2f}s] {msg}",
          file=sys.stderr, flush=True)


def load_peaks() -> Dict[str, Any]:
    with open(os.path.join(cells.BENCH, "peaks.json")) as f:
        return json.load(f)


def check_device(chips: int, peaks: Dict[str, Any]):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform!r} devices")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    if devs[0].device_kind not in peaks:
        raise NoChip(f"no peaks for {devs[0].device_kind!r} in "
                     "bench/peaks.json")
    return devs[0]


class CompileCounter:
    """Counts programs lowered or compiled (cache hits included)."""

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event in COMPILE_EVENTS:
            self.n += 1


def warm(engine) -> None:
    """Compile (or load) every program the window can run: the prefill
    step at each chunk width (prompts of 2, 3, 4, 6 and 10 tokens prefill
    1, 2, 4, 8 and 16 rows), the decode step and the host-side ops around
    it, and the page-table update at each dirty-row bucket."""
    import jax
    import jax.numpy as jnp

    for n in (2, 3, 4, 6, 10):
        engine.submit(list(range(1, n + 1)), max_new_tokens=2)
    engine.run_until_drained()
    rows = 1
    while rows < engine.n_slots:
        idx = np.arange(rows, dtype=np.int32)
        engine.state["table"] = engine._table_scatter(
            engine.state["table"], jnp.asarray(idx),
            jnp.asarray(engine._table[idx]))
        rows *= 2
    jax.block_until_ready(engine.state)


def record_calls(engine, calls: List[Call], tracing: Dict[str, bool]):
    """Wrap the engine's two jitted steps so that each call is logged
    with the rows it serves (``(new tokens, cache length after)``)."""
    step, prefill = engine._step, engine._prefill

    def decode_call(params, state, tokens):
        rows = [(1, r.kv_len + 1) for r in engine._active.values()]
        calls.append(Call("decode", rows, tracing["on"]))
        return step(params, state, tokens)

    def prefill_call(params, state, tokens, n_valid):
        nv = np.asarray(n_valid)
        rows = [(int(nv[r.slot]), r.kv_len)
                for r in engine._active.values() if nv[r.slot] > 0]
        calls.append(Call("prefill", rows, tracing["on"]))
        return prefill(params, state, tokens, n_valid)

    engine._step, engine._prefill = decode_call, prefill_call


class TraceWindow:
    """Profile ``seconds`` of the window, starting at the first arrival
    one second in (so that a prefill falls inside), or at a third of the
    window if none comes."""

    def __init__(self, engine, source, window_s: float, out_dir: str):
        self.engine, self.source = engine, source
        self.seconds = min(TRACE_SECONDS, max(window_s / 3, 0.5))
        self.latest = window_s / 3
        self.dir = out_dir
        self.state = {"on": False}
        self.done = False
        self.t_on = 0.0
        self._ann = None

    def __call__(self, client, elapsed: float) -> None:
        import jax

        if self.state["on"]:
            if elapsed - self.t_on >= self.seconds:
                self.stop()
            return
        if self.done or elapsed < min(1.0, self.latest):
            return
        nxt = self.source.next_due()
        if nxt is None or nxt - elapsed <= 0.01 or elapsed >= self.latest:
            jax.block_until_ready(self.engine.state)
            # host spans only: tracing every Python call slows the host
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self._ann = jax.profiler.TraceAnnotation(tr.WINDOW_SPAN)
            self._ann.__enter__()
            self.state["on"], self.t_on = True, elapsed

    def stop(self) -> None:
        import jax

        if not self.state["on"]:
            return
        jax.block_until_ready(self.engine.state)
        self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.state["on"], self.done = False, True


def build_engine(cell, seed: int, tracer):
    """The engine as the configuration states it, serving weights drawn
    from the seed: the engine draws them from ``prng_key(0)``, and is
    handed the seed's key (``reference.weight_key``) in its place."""
    from unittest import mock

    import jax

    import repro.serving.engine as serving

    conf = cell.config
    ref = cells.load_module("reference", conf["reference"], cell.bench)
    real_key = serving.prng_key

    def key(value):
        return ref.weight_key(seed) if value == 0 else real_key(value)

    with mock.patch.object(serving, "prng_key", key):
        engine = serving.ServeEngine(
            cells.model_config(conf), max_seq_len=conf["max_seq_len"],
            max_slots=conf["max_slots"], pack_weights=True, paged=True,
            kv_page_size=conf["kv_page_size"], paged_attn=True,
            greedy=True, sample_seed=seed, tracer=tracer)
    jax.block_until_ready(engine.params)
    return engine


def serve(cell, seed: int, seconds: float, trace: bool,
          require_chip: bool = True, plant=None,
          save_trace: Optional[str] = None):
    """Set up, serve the window and read the cell's metrics. Returns the
    result line without its verdict, the requests offered, and how many
    programs compiled inside the window. The engine is freed on return.
    ``plant(engine)`` may break the engine before warming (tests)."""
    import jax

    from repro import obs

    peaks_all = load_peaks()
    dev = (check_device(cell.chips, peaks_all) if require_chip
           else jax.devices()[0])
    peaks = peaks_all.get(dev.device_kind) or peaks_all["TPU v5 lite"]
    compiles = CompileCounter()
    conf, traffic = cell.config, cell.traffic
    tracer = obs.Tracer(annotate=trace, ring_capacity=1 << 16)
    engine = build_engine(cell, seed, tracer)
    log(f"engine built: {engine.n_slots} slots, "
        f"{engine.weight_read_bytes / 2**30:.3f} GiB weights")
    if plant is not None:
        plant(engine)
    warm(engine)
    gen = cells.load_module("generators", traffic["kind"], cell.bench)
    source = gen.make(traffic, seed, seconds, conf["model"]["vocab_size"])
    client = Client(engine, source)
    calls: List[Call] = []
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    window = (TraceWindow(engine, source, seconds, trace_dir)
              if trace else None)
    client.setup()
    jax.block_until_ready(engine.state)
    if trace:
        # after set-up, so that only the window's calls are counted
        record_calls(engine, calls, window.state)
    setup_s = time.perf_counter() - T_START
    log(f"set-up {setup_s:.2f}s; window of {seconds}s opens")
    snap0 = engine.metrics_snapshot()
    n0 = compiles.n
    client.window(seconds, hook=window)
    jax.block_until_ready(engine.state)
    if window is not None:
        window.stop()
    in_window = compiles.n - n0
    snap1 = engine.metrics_snapshot()
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    log(f"window closed: {client.t1 - client.t0:.2f}s, "
        f"{snap1['ticks'] - snap0['ticks']} ticks, {in_window} compiles")
    run = Run(cell=cell, seed=seed, seconds=seconds, setup_s=setup_s,
              t0=client.t0, t1=client.t1, requests=source.requests,
              counters={"start": snap0, "end": snap1}, peaks=peaks,
              work=cells.load_module("work", conf["work"], cell.bench),
              calls=calls)
    if trace:
        run.trace = tr.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        if save_trace:
            with open(save_trace, "w") as f:
                json.dump({"cell": cell.name,
                           "trace": tr.trimmed(run.trace, 0.3).to_json(),
                           "calls": [[c.kind, c.rows]
                                     for c in run.traced_calls()]}, f)
    metrics = {}
    for m in cell.metrics(trace):
        value = cells.load_module("metrics", m["name"], cell.bench).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": int(mem)}
    result: Dict[str, Any] = {
        "correct": False, "attempted": client.attempted,
        "failed": client.failed, "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = tr.busy_s(run.trace)
        device["window_s"] = run.trace.window_s
        result["breakdown"] = {"device_ops": tr.top_ops(run.trace),
                               "idle_gaps": tr.idle_gaps(run.trace)}
    # free the engine before the reference takes the device
    client.engine = engine = window = None
    gc.collect()
    log(f"engine freed: {sum(a.nbytes for a in jax.live_arrays())} B "
        "still on the device")
    return result, source.requests, in_window


def run_cell(cell, seed: int, seconds: float, trace: bool,
             require_chip: bool = True, plant=None,
             save_trace: Optional[str] = None,
             control: bool = False) -> Dict[str, Any]:
    """One run of ``cell``: :func:`serve`, then the comparison with the
    reference. Returns the result line as a dict. ``control`` also judges
    the precision-stepped control over the same sample by the same limits
    (``bench/calibrate.py``; the benchmark's own runs never do)."""
    result, requests, in_window = serve(cell, seed, seconds, trace,
                                        require_chip, plant, save_trace)
    t = time.perf_counter()
    conf = cell.config
    verdict = check.verdict(conf, seed, requests,
                            conf["model"]["vocab_size"], control=control)
    verdict["checked"]["compiles_in_window"] = {
        "value": in_window, "op": "<=", "limit": 0}
    log(f"reference compared in {time.perf_counter() - t:.2f}s")
    result["correct"] = verdict["correct"] and in_window == 0
    if control:
        result["control"] = verdict["control"]
    result["checked"] = verdict["checked"]
    return result


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--save-trace", default=None,
                    help="also write the first 0.3 s of the reduced trace "
                         "and the traced calls to this JSON file")
    return ap.parse_args(argv)


def use_compile_cache() -> None:
    """JAX's persistent compilation cache in a fixed directory of the
    checkout, every program in it, so that each run after a cell's first
    loads its programs instead of compiling them. Set before JAX starts;
    the compiler's logs stay off the disk."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    from repro import compat

    compat.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def main(argv=None) -> int:
    args = parse(argv)
    cell = cells.resolve(args.workload)
    use_compile_cache()
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          save_trace=args.save_trace)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    for name, c in result["checked"].items():
        print(f"check {name}: {c['value']} {c['op']} {c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
