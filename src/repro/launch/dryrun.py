"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: for each
cell we ``jit(step).lower(ShapeDtypeStructs).compile()`` on the 16x16
production mesh and the 2x16x16 multi-pod mesh, then record
``memory_analysis()`` (fits-in-HBM proof), ``cost_analysis()`` (FLOPs /
bytes for the roofline), and the collective-byte census parsed from the
compiled HLO.

``--mesh-matrix`` is the CPU-CI face of the same machinery: on a small
forced-host-device count it compiles a reduced config across the mesh
shapes that stress both compat API paths — 1xN (pure TP), Nx1 (pure DP,
incl. the uneven batch fallback), and the 3-axis pod x data x model
multi-pod shape — plus the shard_map collectives (compressed ring
all-reduce, pipeline schedule), so a regression in either shard_map /
mesh-query generation fails CI without hardware.

Usage:
    python -m repro.launch.dryrun                      # all cells
    python -m repro.launch.dryrun --arch qwen3_8b --shape decode_32k
    python -m repro.launch.dryrun --multi-pod          # 512-chip mesh
    python -m repro.launch.dryrun --mode zero          # DP-sharded state
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python -m repro.launch.dryrun --mesh-matrix    # CI smoke

Results are appended as JSON lines under benchmarks/results/dryrun/.
"""

import argparse
import dataclasses
import json
import os
import time
import traceback
from typing import List, Optional, Sequence, Tuple

import jax

from repro import compat
from repro.configs import ARCHS, get_config
from repro.launch.hlo_census import count_ops, hlo_cost
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import build_programs
from repro.models.config import ALL_SHAPES

RESULTS_DIR = os.path.join(
    os.path.dirname(__file__), "..", "..", "..", "benchmarks", "results",
    "dryrun",
)


def cell_skip_reason(cfg, shape) -> Optional[str]:
    if shape.name == "long_500k" and not cfg.sub_quadratic:
        return ("pure full attention: 512k softmax decode excluded by "
                "design (DESIGN.md section 6)")
    return None


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             mode: str = "tp", compression: bool = True,
             kv_bits: int = None) -> dict:
    from repro.models.config import NO_COMPRESSION
    cfg = get_config(arch)
    if not compression:
        cfg = dataclasses.replace(cfg, compression=NO_COMPRESSION)
    if kv_bits:
        cfg = dataclasses.replace(
            cfg, compression=dataclasses.replace(
                cfg.compression, kv_bits=kv_bits))
    shape = next(s for s in ALL_SHAPES if s.name == shape_name)
    reason = cell_skip_reason(cfg, shape)
    rec = {
        "arch": arch, "shape": shape_name, "mode": mode,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "kind": shape.kind, "compression": compression,
    }
    if reason:
        rec.update(status="SKIP", reason=reason)
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    with compat.mesh_context(mesh):
        prog = build_programs(cfg, shape, mesh, mode=mode)
        lowered = prog.lower()
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()

    census = hlo_cost(hlo)
    n_dev = mesh.devices.size
    rec.update(
        status="OK",
        lower_s=round(t_lower, 1),
        compile_s=round(t_compile, 1),
        devices=n_dev,
        # trip-weighted static census (cost_analysis counts while bodies
        # once; see hlo_census docstring) — per device per step
        flops=census["flops"],
        bytes_accessed=census["bytes"],
        xla_cost_flops=float(cost.get("flops", 0.0)),
        xla_cost_bytes=float(cost.get("bytes accessed", 0.0)),
        collectives=census["collectives"],
        memory={
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
        },
        ops=count_ops(hlo),
    )
    return rec


# ---------------------------------------------------------------------------
# CPU-CI mesh-shape matrix
# ---------------------------------------------------------------------------

def mesh_matrix_specs(
        n_devices: int) -> List[Tuple[Tuple[int, ...], Tuple[str, ...]]]:
    """Mesh shapes that cover both degenerate 2-D layouts plus the
    3-axis multi-pod layout when the device count factors."""
    specs = [
        ((1, n_devices), ("data", "model")),       # pure TP
        ((n_devices, 1), ("data", "model")),       # pure DP
    ]
    if n_devices % 4 == 0:
        specs.append(((2, n_devices // 4, 2), ("pod", "data", "model")))
    return specs


def _matrix_collectives_smoke(n_devices: int) -> List[dict]:
    """Compressed ring all-reduce + pipeline schedule through the compat
    shard_map seam — the collectives must produce identical numerics on
    either shard_map generation."""
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.distributed.grad_compress import compressed_psum
    from repro.distributed.pipeline import pipeline_apply

    recs = []
    rng = np.random.default_rng(0)

    x = rng.standard_normal((n_devices, 640)).astype(np.float32)
    mesh = compat.make_mesh((n_devices,), ("data",))
    ring = compat.shard_map(
        lambda xs: compressed_psum(xs[0], "data", 16)[None],
        mesh=mesh, in_specs=P("data", None), out_specs=P("data", None),
        check_replication=False,
    )
    got = np.asarray(jax.jit(ring)(x))
    ref = x.sum(0)
    err = float(np.abs(got - ref).max() / np.abs(ref).max())
    recs.append({"check": "ring_allreduce", "mesh": f"{n_devices}",
                 "status": "OK" if err < 2e-2 else "FAIL",
                 "rel_err": err})

    n_stages, l_per, d = min(n_devices, 4), 2, 16
    pmesh = compat.make_mesh((n_stages,), ("stage",),
                             devices=jax.devices()[:n_stages])
    ws = jnp.asarray(
        rng.standard_normal((n_stages, l_per, d, d)).astype(np.float32)
        * 0.3)

    def block_fn(params, xb):
        for i in range(l_per):
            xb = jnp.tanh(xb @ params[i])
        return xb

    xs = jnp.asarray(rng.standard_normal((8, 4, d)).astype(np.float32))
    got = pipeline_apply(block_fn, ws, xs, pmesh)
    ref = xs
    for s in range(n_stages):
        ref = jax.vmap(lambda mb, s=s: block_fn(ws[s], mb))(ref)
    err = float(jnp.abs(got - ref).max())
    recs.append({"check": "pipeline", "mesh": f"{n_stages}",
                 "status": "OK" if err < 1e-5 else "FAIL",
                 "abs_err": err})
    return recs


def run_mesh_matrix(arch: str = "qwen3_8b") -> List[dict]:
    """Compile one reduced program per matrix mesh shape and run the
    collectives smoke.  Pair with a small
    ``--xla_force_host_platform_device_count``; returns one record per
    cell with status OK/FAIL."""
    n = len(jax.devices())
    if n > 32:
        # without an explicit small override the module default of 512
        # forced host devices applies — a 512-way CPU matrix is an
        # hours-long hang, not a smoke
        raise SystemExit(
            f"mesh matrix on {n} devices is not a smoke test; run under "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8 (or <=32)")
    cfg = get_config(arch).reduced()
    base_train = next(s for s in ALL_SHAPES if s.kind == "train")
    base_decode = next(s for s in ALL_SHAPES if s.kind == "decode")
    # batch 4 on an Nx1 mesh is deliberately indivisible by DP=8: it
    # exercises the drop_indivisible fallback on every run
    train_shape = dataclasses.replace(
        base_train, global_batch=4, seq_len=128)
    decode_shape = dataclasses.replace(
        base_decode, global_batch=4, seq_len=256)

    records = []
    for (shape_t, axes), prog_shape in zip(
            mesh_matrix_specs(n),
            (decode_shape, train_shape, train_shape)):
        tag = "x".join(map(str, shape_t))
        rec = {"check": "compile", "arch": arch, "mesh": tag,
               "axes": "/".join(axes), "kind": prog_shape.kind}
        try:
            mesh = compat.make_mesh(shape_t, axes)
            t0 = time.time()
            with compat.mesh_context(mesh):
                prog = build_programs(cfg, prog_shape, mesh)
                compiled = prog.lower().compile()
                census = hlo_cost(compiled.as_text())
            rec.update(
                status="OK", compile_s=round(time.time() - t0, 1),
                flops=census["flops"],
                collective_bytes=census["collectives"]["total_bytes"],
            )
        except Exception as e:  # noqa: BLE001 - report and continue
            rec.update(status="FAIL",
                       error=f"{type(e).__name__}: {e}",
                       trace=traceback.format_exc()[-2000:])
        records.append(rec)
    records.extend(_matrix_collectives_smoke(n))
    return records


def _default_host_devices() -> None:
    """512 forced host devices unless the caller chose a count. Appended
    rather than assigned: CPU CI drives the mesh matrix under its own
    --xla_force_host_platform_device_count, which must win, while
    unrelated user flags (--xla_dump_to=...) must not silently drop the
    512-chip production default. Runs before the first backend use."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=512").strip()


def main() -> None:
    _default_host_devices()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="one arch id (default all)")
    ap.add_argument("--shape", default=None, help="one shape (default all)")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--mode", default="tp", choices=["tp", "zero"])
    ap.add_argument("--no-compression", action="store_true",
                    help="paper-baseline: strip all packing from the config")
    ap.add_argument("--kv-bits", type=int, default=None,
                    help="override the KV-cache packing width")
    ap.add_argument("--mesh-matrix", action="store_true",
                    help="CPU-CI mesh-shape matrix (1xN, Nx1, multi-pod) "
                         "+ shard_map collectives smoke; honors the "
                         "caller's --xla_force_host_platform_device_count")
    ap.add_argument("--matrix-arch", default="qwen3_8b")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    if args.mesh_matrix:
        print(f"compat: {json.dumps(compat.support_matrix())}", flush=True)
        recs = run_mesh_matrix(args.matrix_arch)
        bad = 0
        for rec in recs:
            bad += rec["status"] != "OK"
            detail = rec.get("error", "") or (
                f"compile={rec.get('compile_s', '-')}s "
                f"coll={rec.get('collective_bytes', 0):.3e}B"
                if rec["check"] == "compile" else
                f"err={rec.get('rel_err', rec.get('abs_err'))}")
            print(f"[{rec['status']}] {rec['check']} mesh={rec['mesh']} "
                  f"{detail}", flush=True)
        if bad:
            raise SystemExit(f"{bad} mesh-matrix cell(s) failed")
        print("mesh-matrix complete")
        return

    archs = [args.arch] if args.arch else [a for a in ARCHS
                                           if a != "paper_native"]
    shapes = ([args.shape] if args.shape
              else [s.name for s in ALL_SHAPES])
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    os.makedirs(RESULTS_DIR, exist_ok=True)
    out_path = args.out or os.path.join(RESULTS_DIR, "cells.jsonl")

    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch} x {shape} x {'2x16x16' if mp else '16x16'}"
                try:
                    rec = run_cell(arch, shape, mp, args.mode,
                                   compression=not args.no_compression,
                                   kv_bits=args.kv_bits)
                except Exception as e:  # noqa: BLE001 - report and continue
                    rec = {
                        "arch": arch, "shape": shape, "mode": args.mode,
                        "mesh": "2x16x16" if mp else "16x16",
                        "status": "FAIL",
                        "error": f"{type(e).__name__}: {e}",
                        "trace": traceback.format_exc()[-2000:],
                    }
                    failures += 1
                with open(out_path, "a") as f:
                    f.write(json.dumps(rec) + "\n")
                status = rec["status"]
                extra = ""
                if status == "OK":
                    extra = (
                        f"flops={rec['flops']:.3e} "
                        f"bytes={rec['bytes_accessed']:.3e} "
                        f"coll={rec['collectives']['total_bytes']:.3e}B "
                        f"({rec['compile_s']}s)"
                    )
                elif status == "FAIL":
                    extra = rec["error"][:160]
                print(f"[{status}] {tag} {extra}", flush=True)
    if failures:
        raise SystemExit(f"{failures} cell(s) failed")
    print("dry-run complete")


if __name__ == "__main__":
    main()
