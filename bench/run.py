"""Run one benchmark cell on the chip and print its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name from ``BENCHMARK.json``: the cell
names its configuration (``configs[].file``) and its traffic mix
(``bench/traffic/<traffic>.json``); the configuration's ``kind`` names the
code that drives it (``bench/kinds/<kind>.py``); each per-layer metric is
read by ``bench/metrics/<metric>.py``.  Adding a cell, a configuration or a
metric adds files and edits none.

A run: find the chips (none, or fewer than the cell asks for: exit 2 with
no result); build the cell and run its first chunk, which compiles
(``setup_s`` ends here); call the cell for ``--seconds``, one whole chunk
at a time; read the device's peak memory; free the program's state; run
the plain reference over the first chunk and compare.  The last line of
standard output is the JSON result; the numbers compared, each beside its
limit, are the last lines of standard error and the result's last key.
With ``--trace 1`` the window runs under the profiler and the result holds
the per-layer metrics and a breakdown instead of the end-to-end ones.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Callable, Dict, List  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


#: the ``jax.monitoring`` event of a program served from the compile cache
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def _load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.exists():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(root: Path, name: str):
    """(benchmark, cell, config entry, config, traffic) for cell ``name``."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = json.loads((root / entry["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())
    return bench, cell, entry, config, traffic


def tpu_devices(chips: int) -> List:
    """The first ``chips`` TPU devices; raises :class:`NoChip` otherwise."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX runs on {devices[0].platform}")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX has "
                     f"{len(devices)}")
    return devices[:chips]


def peaks_of(root: Path, kind: str) -> Dict[str, float]:
    table = json.loads((root / "bench" / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise NoChip(f"device kind {kind!r} is not in bench/peaks.json")
    return table["devices"][kind]


def enable_compile_cache(root: Path) -> None:
    """JAX's persistent cache at the checkout's fixed ``.jax_cache`` (or
    where ``JAX_COMPILATION_CACHE_DIR`` says), every program cached."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def per_layer_for(bench: Dict, cell: Dict, e2e_names) -> List[Dict]:
    """The per-layer metrics this cell reports."""
    out = []
    for m in bench["per_layer"]:
        if "workloads" in m:
            if cell["name"] in m["workloads"]:
                out.append(m)
        elif m["moves"] in e2e_names:
            out.append(m)
    return out


def end_to_end_for(bench: Dict, cell: Dict) -> List[Dict]:
    return [m for m in bench["end_to_end"]
            if "workloads" not in m or cell["name"] in m["workloads"]]


def run(argv=None, *, root: Path = ROOT,
        devices_fn: Callable[[int], List] = tpu_devices,
        log=None) -> Dict:
    """One run; returns the result object (without printing it)."""
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)
    log = log or (lambda s: print(s, file=sys.stderr, flush=True))

    bench, cell, entry, config, traffic = find_cell(root, args.workload)
    devices = devices_fn(int(cell["chips"]))
    dev = devices[0]
    peaks = peaks_of(root, dev.device_kind)
    log(f"[bench] {cell['name']}: platform={dev.platform} "
        f"kind={dev.device_kind} count={len(devices)} seed={args.seed}")

    import jax
    enable_compile_cache(root)
    from repro.analysis import recompile
    compiles: List[float] = []
    recompile.subscribe(lambda event, s: compiles.append(s))
    hits: List[str] = []
    jax.monitoring.register_event_listener(
        lambda event, **kw: hits.append(event) if event == CACHE_HIT
        else None)

    kind = _load_module(root / "bench" / "kinds" / f"{config['kind']}.py",
                        f"bench_kind_{config['kind']}")
    t_build = time.perf_counter()
    c = kind.Cell(config, traffic, args.seed, int(cell["chips"]))
    t_warm = time.perf_counter()
    c.warm()
    # what set-up built stays alive: move it out of the collector's way,
    # so that no full collection lands in the window
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - T0
    log(f"[bench] set-up {setup_s:.3f} s: start {t_build - T0:.3f} s, "
        f"build {t_warm - t_build:.3f} s, first chunk "
        f"{setup_s - (t_warm - T0):.3f} s; {len(compiles)} compiles, "
        f"{len(hits)} compile-cache hits")

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if args.trace \
        else None
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    c0 = len(compiles)
    units = 0
    t_start = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        while True:
            units += c.call()
            if time.perf_counter() - t_start >= args.seconds:
                break
        c.sync()
    seconds = time.perf_counter() - t_start
    gc.unfreeze()
    in_window = len(compiles) - c0
    if trace_dir:
        jax.profiler.stop_trace()
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
    e2e = c.end_to_end(units, seconds)
    counts = c.counts()
    log(f"[bench] window {seconds:.3f} s, {units} {c.unit}, "
        f"{in_window} compiles, peak {peak} B, {e2e}")

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    metrics: Dict[str, Dict] = {}
    breakdown = None
    e2e_defs = end_to_end_for(bench, cell)
    if trace_dir:
        from bench import trace as tracelib
        tr = tracelib.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        device.update(busy_s=tr.busy_s(), window_s=tr.window_s)
        ctx = {"cell": cell, "config": config, "traffic": traffic,
               "peaks": peaks, "chips": len(devices), "trace": tr,
               "units": units, "counts": counts,
               "compiles_in_window": in_window,
               "memory_peak_bytes": peak}
        for m in per_layer_for(bench, cell, {d["name"] for d in e2e_defs}):
            reader = _load_module(root / "bench" / "metrics"
                                  / f"{m['name']}.py",
                                  "bench_metric_" + m["name"].replace(".",
                                                                      "_"))
            value = reader.read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        breakdown = {"device_ops": tr.top_ops(10),
                     "idle_gaps": tr.idle_gaps(10)}
    else:
        for m in e2e_defs:
            value = setup_s if m["name"] == "setup_s" else e2e[m["name"]]
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    c.release()
    gc.collect()
    values = c.compare(c.readings, c.reference())
    from bench import check
    correct, rows = check.verdict(values, check.load_limits(root,
                                                            cell["name"]))
    for r in rows:
        log(f"[check] {r['name']}: {r['value']!r} (limit {r['limit']!r})")
    result = {"correct": correct, "attempted": units,
              "failed": 0 if correct else units, "metrics": metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {r["name"]: {"value": r["value"], "limit": r["limit"]}
                        for r in rows}
    return result


def main(argv=None) -> int:
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    try:
        result = run(argv)
    except NoChip as e:
        print(f"[bench] {e}: no result", file=sys.stderr, flush=True)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
