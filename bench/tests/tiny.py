"""A toy copy of the benchmark for tests on the CPU: the harness's own
files, plus a configuration at toy widths, two small mixes and one more
metric, each added as a new file, in a temporary directory with a
manifest of two cells.

``python -m bench.tests.tiny <cell> <seconds> <seed> <scenario> ...`` runs
scenarios in one process, with a compilation cache of its own in
a temporary directory, and prints one JSON line per scenario.
"""
from __future__ import annotations

import json
import pathlib
import shutil
import sys
import tempfile

from bench import cell as cells

DATA = pathlib.Path(__file__).resolve().parent / "data"
CELLS = {"tiny.chat": "tiny_chat", "tiny.gen": "tiny_gen"}
RENAME = {"qwen3_8b.chat": "tiny.chat", "granite_34b.long_gen": "tiny.gen"}


def make_bench(root: pathlib.Path) -> pathlib.Path:
    """Copy ``bench/`` under ``root`` with the toy configuration and mixes
    added as files, and a manifest of the two toy cells beside it."""
    bench = root / "bench"
    shutil.copytree(cells.BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(DATA / "tiny.json", bench / "configs" / "tiny.json")
    for traffic in CELLS.values():
        shutil.copy(DATA / f"{traffic}.json",
                    bench / "traffic" / f"{traffic}.json")
    shutil.copy(DATA / "requests_served.py",
                bench / "metrics" / "requests_served.py")
    man = cells.load_manifest()
    man["end_to_end"].append(
        {"name": "requests_served", "unit": "requests", "better": "higher",
         "bound": 0.25, "source": "host_clock"})
    man["workloads"] = [
        {"name": name, "config": "tiny", "traffic": traffic, "chips": 1,
         "why": "toy cell for tests"} for name, traffic in CELLS.items()]
    for m in man["end_to_end"] + man["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [RENAME[w] for w in m["workloads"]]
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(man, f, indent=1)
    return bench


def _decode_unchanged(engine):
    """Fault: the decode step returns the cache it was given."""
    import jax

    step = jax.jit(engine.lm.decode_step)

    def broken(params, state, tokens):
        logits, _ = step(params, state, tokens)
        return logits, state
    engine._step = broken


def _token_altered(engine):
    """Fault: every served token is shifted by one where it is chosen."""
    step = engine._step

    def broken(params, state, tokens):
        import jax.numpy as jnp

        logits, state = step(params, state, tokens)
        return jnp.roll(logits, 1, axis=-1), state
    engine._step = broken


def _half_batch(engine):
    """Fault: the second half of the slots gets the first half's logits."""
    step = engine._step

    def broken(params, state, tokens):
        logits, state = step(params, state, tokens)
        half = logits.shape[0] // 2
        return logits.at[half:].set(logits[:half]), state
    engine._step = broken


FAULTS = {"decode_state_unchanged": _decode_unchanged,
          "token_altered": _token_altered,
          "half_batch_left_out": _half_batch}


def main(argv) -> int:
    """``tiny.py <cell> <seconds> <seed> <scenario> ...`` in groups of
    four; a scenario is ``none``, a fault of :data:`FAULTS`, or
    ``control`` (a sound run that also reads the precision-stepped
    control over the tokens it served)."""
    import jax

    tmp = pathlib.Path(tempfile.mkdtemp(prefix="bench-tiny-"))
    jax.config.update("jax_compilation_cache_dir", str(tmp / "cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    bench = make_bench(tmp)
    from bench import run as bench_run

    man = cells.load_manifest(tmp)
    for i in range(0, len(argv), 4):
        name, seconds, seed, fault = argv[i:i + 4]
        cell = cells.resolve(name, man, bench)
        res = bench_run.run_cell(cell, int(seed), float(seconds), False,
                                 require_chip=False,
                                 plant=FAULTS.get(fault),
                                 control=fault == "control")
        print(json.dumps({"cell": name, "fault": fault, **res}),
              flush=True)
    shutil.rmtree(tmp, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
