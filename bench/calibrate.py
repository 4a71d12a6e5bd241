"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 11,12,... \
        [--control-seeds 3] [--fault-seeds 3] [--out <file>.json]
    python3 bench/calibrate.py --workload <cell> --limits-from <file>.json \
        ... [--write]

For every seed: the program's first chunk at the cell's own size against
the reference (the sound runs, which set the lower reading).  For the first
``--control-seeds`` seeds also the control -- the reference computed in the
precision below the configuration's, in the program's place -- and for the
first ``--fault-seeds`` each fault the cell can have, planted in the
reference put in the program's place (the readings that set the upper
end).  ``--limits-from`` sets the limits from such files.  One process holds the chip
throughout; the benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]

#: faults each kind of cell can have, planted in its reference
FAULTS = {"train": ("unchanged", "half_batch", "altered")}


def limits_from(runs, fault_factor: float = 10.0) -> dict:
    """Each number's lower reading (the largest of the sound runs), upper
    reading (the smallest control reading, where that is 3x the lower or
    more, and the smallest reading of each fault that reads
    ``fault_factor`` x the lower or more -- 3x for a state left unchanged)
    and limit: two thirds of the way from the lower to the upper in log
    scale, so that there is more room above the lower, rounded down to two
    significant digits."""
    import math
    out = {}
    names = runs[0]["sound"][0]["gaps"].keys()
    for name in names:
        lower = max(r["gaps"][name] for r in runs_of(runs, "sound"))
        cands = []
        ctl = [r["gaps"][name] for r in runs_of(runs, "control")]
        if ctl and min(ctl) >= 3 * lower:
            cands.append((min(ctl), "control"))
        by_fault = {}
        for r in runs_of(runs, "faults"):
            by_fault.setdefault(r["fault"], []).append(r["gaps"][name])
        for fault, vals in sorted(by_fault.items()):
            need = 3.0 if fault == "unchanged" else fault_factor
            if min(vals) >= need * lower:
                cands.append((min(vals), f"fault {fault}"))
        row = {"lower": lower, "sound_runs": len(runs_of(runs, "sound"))}
        if cands:
            upper, frm = min(cands)
            raw = lower ** (1 / 3) * upper ** (2 / 3)
            exp = math.floor(math.log10(raw)) - 1
            row.update(upper=upper, upper_from=frm,
                       limit=round(math.floor(raw / 10 ** exp) * 10 ** exp,
                                   -exp))
        out[name] = row
    return out


def runs_of(runs, key):
    return [r for run in runs for r in run[key]]


def _plain(readings):
    """Readings as JSON lists (the per-leaf detail behind each gap)."""
    import numpy as np
    return {k: np.asarray(v).tolist() for k, v in readings.items()}


def main(argv=None) -> int:
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import run as runner
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default=None)
    ap.add_argument("--limits-from", nargs="*", default=None,
                    help="calibration files to set the limits from "
                         "(no chip needed)")
    ap.add_argument("--write", action="store_true",
                    help="with --limits-from: write bench/limits/<cell>.json")
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--fault-seeds", type=int, default=3)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    _, cell, _, config, traffic = runner.find_cell(ROOT, args.workload)
    if args.limits_from:
        runs = [json.loads(Path(p).read_text()) for p in args.limits_from]
        rows = limits_from(runs)
        doc = {"limits": {k: r.get("limit") for k, r in rows.items()},
               "readings": rows,
               "from": [Path(p).name for p in args.limits_from]}
        print(json.dumps(doc, indent=1))
        if args.write:
            (ROOT / "bench" / "limits" / f"{args.workload}.json").write_text(
                json.dumps(doc, indent=1) + "\n")
        return 0
    devices = runner.tpu_devices(int(cell["chips"]))
    runner.enable_compile_cache(ROOT)
    kind = runner._load_module(ROOT / "bench" / "kinds"
                               / f"{config['kind']}.py", "kind")
    control = config["control_matmul"]
    out = {"workload": args.workload, "device": devices[0].device_kind,
           "sound": [], "control": [], "faults": []}
    refs = {}
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        c = kind.Cell(config, traffic, seed, int(cell["chips"]))
        c.warm()
        peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in devices)
        prog = c.readings
        c.release()
        ref = c.reference(cache=refs)
        row = {"seed": seed, "gaps": c.compare(prog, ref),
               "peak_bytes": peak, "seconds": time.perf_counter() - t0,
               "program": _plain(prog), "reference": _plain(ref)}
        out["sound"].append(row)
        print(json.dumps({k: row[k] for k in ("seed", "gaps", "peak_bytes",
                                               "seconds")}), flush=True)
        if i < args.control_seeds:
            ctl = c.reference(cache=refs, precision=control)
            row = {"seed": seed, "gaps": c.compare(ctl, ref),
                   "control": _plain(ctl)}
            out["control"].append(row)
            print("control", json.dumps(row["gaps"]), flush=True)
        if i < args.fault_seeds:
            for fault in FAULTS[config["kind"]]:
                bad = c.reference(cache=refs, fault=fault)
                row = {"seed": seed, "fault": fault,
                       "gaps": c.compare(bad, ref), "readings": _plain(bad)}
                out["faults"].append(row)
                print("fault", json.dumps({k: row[k] for k in
                                           ("seed", "fault", "gaps")}),
                      flush=True)
        del c
        if args.out:
            # after every seed, so that a run cut short keeps what it read
            Path(args.out).parent.mkdir(parents=True, exist_ok=True)
            Path(args.out).write_text(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
