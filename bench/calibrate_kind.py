"""``bench/calibrate.py`` for a cell whose kind lists its own faults.

    python3 bench/calibrate_kind.py --workload <cell> --seeds ... [...]

Takes the same arguments; the faults planted for the cell's kind are the
``FAULTS`` its module (``bench/kinds/<kind>.py``) names.
"""
from __future__ import annotations

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import calibrate, run as runner
    args = sys.argv[1:] if argv is None else argv
    cell = args[args.index("--workload") + 1]
    _, _, _, config, _ = runner.find_cell(ROOT, cell)
    kind = runner._load_module(ROOT / "bench" / "kinds"
                               / f"{config['kind']}.py", "kind_faults")
    calibrate.FAULTS.setdefault(config["kind"], tuple(kind.FAULTS))
    return calibrate.main(args)


if __name__ == "__main__":
    raise SystemExit(main())
