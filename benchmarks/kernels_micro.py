"""Per-op microbenchmark of the Pallas kernels against their jnp oracles.

Times each runtime-facing kernel — flash attention (the `use_pallas`
serving forward), the CKA Gram-term probe (SimFreeze's drift metric) and
the RWKV wkv recurrence — next to its `ref.py` oracle,
and records the parity error alongside, so the bench artifact tracks
both the per-op cost *and* that the kernels still agree with the math
they replace. The kernels run in Pallas interpret mode on CPU and compiled
on TPU (`repro.kernels.resolve_interpret`; the artifact's `interpret`
field records which). On CPU the numbers are emulation costs, not device
timings — the column exists for trajectory tracking (a kernel whose
interpret time explodes got structurally slower).

    PYTHONPATH=src python benchmarks/kernels_micro.py [--iters 5]

Writes ``BENCH_kernels_micro.json`` at the repo root (CI uploads it as
an artifact next to the workload sweep).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Callable, Dict, List

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import numpy as np

from repro.kernels import resolve_interpret

SCHEMA_VERSION = 1
DEFAULT_OUT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..",
                 "BENCH_kernels_micro.json"))

#: Numeric fields every cell must carry (schema contract with CI).
CELL_FIELDS = ("pallas_ms", "ref_ms", "max_abs_err", "iters")


def _time(fn: Callable, iters: int) -> float:
    """Median wall ms per call, after one warmup (compile) call."""
    jax.block_until_ready(fn())
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        times.append((time.perf_counter() - t0) * 1e3)
    return float(np.median(times))


def _cases(seed: int) -> List[Dict]:
    rng = np.random.default_rng(seed)
    f32 = lambda *s: rng.standard_normal(s).astype(np.float32)

    cases = []

    # flash attention at the ViT serving shape (B=8 reduced images,
    # S=65 patch tokens) — the exact call `use_pallas` routes
    from repro.kernels.attention.ops import flash_attention
    from repro.kernels.attention.ref import attention_ref
    q, k, v = f32(8, 65, 3, 64), f32(8, 65, 3, 64), f32(8, 65, 3, 64)
    cases.append(dict(
        op="flash_attention", shape="B8xS65xH3xhd64 causal=False",
        pallas=lambda: flash_attention(q, k, v, causal=False),
        ref=lambda: attention_ref(q, k, v, causal=False)))

    # CKA ratio at the SimFreeze probe shape (one probe batch of
    # activations, flattened tokens x width) — the scalar the drift
    # detector actually consumes, so parity is in CKA units
    from repro.kernels.cka.ops import cka
    from repro.kernels.cka.ref import cka_ref
    x, y = f32(520, 192), f32(520, 192)
    cases.append(dict(
        op="cka", shape="520x192",
        pallas=lambda: cka(x, y),
        ref=lambda: cka_ref(x, y)))

    # RWKV wkv recurrence (the SSM zoo's sequential core)
    from repro.kernels.rwkv.ops import wkv
    from repro.kernels.rwkv.ref import wkv_ref
    r, kk, vv = f32(2, 128, 2, 64), f32(2, 128, 2, 64), f32(2, 128, 2, 64)
    logw = -np.exp(f32(2, 128, 2, 64) * 0.1).astype(np.float32)
    u = f32(2, 64)
    cases.append(dict(
        op="rwkv_wkv", shape="B2xT128xH2xhd64",
        pallas=lambda: wkv(r, kk, vv, logw, u, bt=64),
        ref=lambda: wkv_ref(r, kk, vv, logw, u)))
    return cases


def run(iters: int = 5, seed: int = 0) -> Dict:
    cells = []
    for case in _cases(seed):
        out_p = np.asarray(jax.tree.leaves(case["pallas"]())[0])
        out_r = np.asarray(jax.tree.leaves(case["ref"]())[0])
        err = float(np.max(np.abs(out_p - out_r)))
        cell = {
            "op": case["op"], "shape": case["shape"],
            "pallas_ms": round(_time(case["pallas"], iters), 3),
            "ref_ms": round(_time(case["ref"], iters), 3),
            "max_abs_err": err, "iters": iters,
        }
        cells.append(cell)
        print(f"kernels_micro,{cell['op']},{cell['shape']},"
              f"pallas={cell['pallas_ms']}ms ref={cell['ref_ms']}ms "
              f"err={err:.2e}", flush=True)
    return {
        "schema_version": SCHEMA_VERSION, "suite": "kernels_micro",
        "seed": seed, "created_unix": int(time.time()),
        "jax_version": jax.__version__,
        "interpret": resolve_interpret(), "cells": cells,
    }


def validate_bench(doc: Dict) -> List[str]:
    """Return a list of schema violations (empty = valid)."""
    errors: List[str] = []
    if not isinstance(doc, dict):
        return ["document is not a JSON object"]
    if doc.get("schema_version") != SCHEMA_VERSION:
        errors.append(f"schema_version != {SCHEMA_VERSION}")
    if doc.get("suite") != "kernels_micro":
        errors.append("suite != 'kernels_micro'")
    cells = doc.get("cells") or []
    if not isinstance(cells, list) or len(cells) < 3:
        errors.append("cells must list at least the 3 kernel ops")
        return errors
    for i, cell in enumerate(cells):
        if not cell.get("op") or not cell.get("shape"):
            errors.append(f"cell {i}: missing op/shape")
        for f in CELL_FIELDS:
            v = cell.get(f)
            if not isinstance(v, (int, float)) or v != v or v < 0:
                errors.append(f"cell {i}: field {f!r} missing or not a "
                              f"non-negative finite number (got {v!r})")
    return errors


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=DEFAULT_OUT)
    ap.add_argument("--validate", metavar="PATH",
                    help="validate an existing artifact and exit")
    args = ap.parse_args()

    from repro.launch.platform import bootstrap
    bootstrap()

    if args.validate:
        with open(args.validate) as f:
            errors = validate_bench(json.load(f))
        for e in errors:
            print(f"SCHEMA ERROR: {e}", file=sys.stderr)
        print(f"{args.validate}: " +
              ("INVALID" if errors else "schema valid"))
        return 1 if errors else 0

    doc = run(iters=args.iters, seed=args.seed)
    errors = validate_bench(doc)
    if errors:
        for e in errors:
            print(f"SCHEMA ERROR: {e}", file=sys.stderr)
        return 1
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"# wrote {args.out}: {len(doc['cells'])} kernel cells")
    return 0


if __name__ == "__main__":
    sys.exit(main())
