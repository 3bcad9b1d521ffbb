#!/usr/bin/env python
"""End-to-end serving driver: a small LM served with batched requests
through the elastic observer pool (replicas on revocable spot capacity,
scaled online by the paper's Algorithm 1).

    PYTHONPATH=src python examples/elastic_serving.py
"""
from repro.launch.serve import main as serve_main
from repro import compile_cache


def main():
    compile_cache.enable()
    return serve_main(["--arch", "smollm-360m", "--requests", "48",
                       "--batch", "8", "--prompt-len", "32",
                       "--gen-len", "8", "--revoke-p", "0.15"])


if __name__ == "__main__":
    raise SystemExit(main())
