"""End-to-end driver: train a language model with DASHA for a few hundred
steps (the deliverable-(b) scenario; scaled to this CPU container).

    PYTHONPATH=src python examples/train_lm.py [--steps 300] [--arch ...]

This wraps the production launcher (repro.launch.train), which runs
entirely through the compiled run driver (DESIGN.md §10): batches are
drawn inside the jitted scan, metrics stream as named traces, and the
checkpoint hook fires between chunks.  On a TPU the same entry point
takes --full to select the published config (--layers N cuts its depth).

``REPRO_EXAMPLE_ROUNDS`` overrides the step count (the CI smoke path).
"""
import argparse
import os

from repro.launch.train import main as train_main

if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int,
                    default=int(os.environ.get("REPRO_EXAMPLE_ROUNDS", 300)))
    ap.add_argument("--arch", default="starcoder2-3b")
    args, rest = ap.parse_known_args()
    train_main([
        "--arch", args.arch, "--steps", str(args.steps),
        "--nodes", "4", "--batch", "2", "--seq", "128",
        "--gamma", "0.003", "--compression", "0.0625",
        "--server-opt", "adam", "--log-every", "25", *rest])
