"""Readings that the limits of the check are set from, at a cell's own
size, in one process (set-up is paid once; every seed after the first
reuses the built programs).

    python3 benchmarks/chip/calibrate.py --workload mbv2.nc.etuner \
        --seeds 101 102 ... --faults 3

For each seed: one kept session of the program, and every number of
`refcheck.compare` for it (the lower readings). For the first `--faults`
seeds also, each against the same reference:
- control: the reference in the precision below the configuration's
  (bfloat16 for the model, "high" for the CKA kernel's terms) in the
  program's place;
- half_batch: the reference on the first half of every batch;
- frozen_trains: the reference training every unit, the frozen ones too;
- stale_publish: the reference serving each request with the params of
  one train-step call earlier;
- answer_altered: the program's served logits moved to the next request,
  its CKA kernel terms to the next sampled call and its CKA values to the
  next unit.
A state left unchanged reads 1 on the leaf gaps by construction and needs
no run. One JSON object per line on stdout.
"""
import argparse
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import harness  # noqa: E402


def altered(obs):
    import refcheck

    keys = sorted(obs.logits)
    moved = {k: obs.logits[keys[(i + 1) % len(keys)]]
             for i, k in enumerate(keys)}
    roll = (lambda a: None if a is None else np.roll(a, 1, axis=0))
    return refcheck.Observed(obs.losses, obs.call_losses, obs.m_norms,
                             obs.dp_norms, obs.final_dp_norms, moved,
                             roll(obs.cka), roll(obs.kernel))


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--faults", type=int, default=3)
    args = p.parse_args(argv)
    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    cell = harness.load_cell(bench, args.workload)
    harness.use_program()
    import refcheck
    from repro.launch.platform import bootstrap

    bootstrap()
    for i, seed in enumerate(args.seeds):
        session = harness.Session(cell, seed)
        try:
            log = session.run(keep=True)
        finally:
            session.close()
        want = refcheck.replay(cell.ref, cell.doc, session.params, log)
        obs = refcheck.observed(log)
        rows = {"program": {**refcheck.compare(obs, want),
                            "images_per_s": session.images(log) / log.wall_s}}
        if i < args.faults:
            def stand_in(**kw):
                return refcheck.compare(refcheck.replay(
                    cell.ref, cell.doc, session.params, log, **kw).observed,
                    want)

            rows["control"] = stand_in(control=True)
            rows["half_batch"] = stand_in(batch_rows=len(
                log.calls[0].batches[0]["labels"]) // 2)
            rows["frozen_trains"] = stand_in(masks_off=True)
            rows["stale_publish"] = stand_in(publish_lag=1)
            rows["answer_altered"] = refcheck.compare(altered(obs), want)
        frozen = sum(1 for c in log.calls if c.flags and any(c.flags))
        for kind, numbers in rows.items():
            print(json.dumps({"seed": seed, "kind": kind,
                              "session_s": log.wall_s,
                              "rounds": log.result.rounds,
                              "calls_under_freeze": frozen,
                              **numbers}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
