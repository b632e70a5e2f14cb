"""Serve a small model with batched requests behind uBFT (the paper's kind
of application: latency-critical serving made Byzantine-tolerant for ~10 µs
of SMR overhead).

    PYTHONPATH=src python examples/serve_replicated.py
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.launch import serve as serve_mod


def main() -> None:
    serve_mod.main(["--arch", "gemma3-1b", "--smoke",
                    "--requests", "12", "--batch", "4", "--gen", "6"])


if __name__ == "__main__":
    main()
