#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Builds the cell's deployed INT8 model from the seed on the chip, serves
its traffic through the program's paged engine for ``--seconds``, checks
the served tokens against the plain reference, and prints one JSON
object as the last line of standard output: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics read from a device trace),
``device`` and, last, ``check`` (each compared number beside its limit).
Exits non-zero without a result when JAX finds no TPU or fewer chips
than the cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def prepare():
    """The harness module with the chip's compile cache set up, or None
    (after one line on standard error) without the program or a TPU."""
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chipbench: no program under {ROOT / 'src'}", file=sys.stderr)
        return None
    # The compile cache lives at a fixed path inside the checkout.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no logs in /tmp
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax

    from chipbench import harness
    from repro.launch.serve import enable_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chipbench: no TPU found (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return None
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return harness


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    harness = prepare()
    if harness is None:
        return 2
    out = harness.run(ROOT, args.workload, args.seed, args.seconds,
                      bool(args.trace), t_start=T_START)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
