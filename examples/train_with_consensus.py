#!/usr/bin/env python
"""End-to-end training driver: a reduced llama config trained for a few
hundred steps with BW-Raft-committed checkpoints, a simulated pod failure
(elastic data parallelism), and restart-from-committed.

    PYTHONPATH=src python examples/train_with_consensus.py
"""
import shutil

from repro.launch.train import main as train_main
from repro import compile_cache

CKPT = "/tmp/repro_example_ckpt"


def main():
    compile_cache.enable()
    shutil.rmtree(CKPT, ignore_errors=True)
    print("=== phase 1: train 200 steps, kill pod 1 at step 60 ===")
    train_main(["--arch", "llama3.2-1b", "--steps", "200",
                "--ckpt-every", "50", "--ckpt-dir", CKPT,
                "--kill-at", "60", "--batch", "8", "--seq", "64"])
    print("\n=== phase 2: restart from the consensus-committed checkpoint "
          "and continue to 260 ===")
    train_main(["--arch", "llama3.2-1b", "--steps", "260",
                "--ckpt-every", "50", "--ckpt-dir", CKPT,
                "--resume", "--batch", "8", "--seq", "64"])
    print("\nOK — restart path restored the digest-checked committed step")


if __name__ == "__main__":
    raise SystemExit(main())
