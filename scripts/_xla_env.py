"""Shared XLA/runtime environment surface for the deployment scripts.

XLA reads ``XLA_FLAGS`` (and the other runtime env vars) exactly once,
when the backend initializes on first jax import — so deployment flags
must land in ``os.environ`` *before* anything imports ``repro.core``.
The scripts therefore parse args and call :func:`apply` first, and only
then import the engine inside ``main()``.

Typical CPU-serving knobs (composed, not replaced — anything already in
``XLA_FLAGS`` is kept):

    --xla-flags "--xla_cpu_multi_thread_eigen=false \
                 intra_op_parallelism_threads=1"
    --xla-flags "--xla_force_host_platform_device_count=8"
    --env TF_CPP_MIN_LOG_LEVEL=3 --env REPRO_DECODE_BACKEND=batched

:func:`apply` also places JAX's persistent compilation cache: where
``JAX_COMPILATION_CACHE_DIR`` is set it is left alone, and otherwise the
cache goes to ``.jax_cache/`` at the root of the checkout — a fixed path,
since the path is part of what a later process must find again.  Every
compiled program is kept, however quick its compile (the decode buckets
each compile in about a second).
"""
from __future__ import annotations

import argparse
import os

#: compile cache used when JAX_COMPILATION_CACHE_DIR is not set
CACHE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), ".jax_cache")


def add_args(ap: argparse.ArgumentParser) -> None:
    g = ap.add_argument_group(
        "runtime environment",
        "applied before the engine (and therefore XLA) initializes")
    g.add_argument("--xla-flags", default=None, metavar="FLAGS",
                   help="flags appended to XLA_FLAGS, e.g. "
                        '"--xla_cpu_multi_thread_eigen=false '
                        'intra_op_parallelism_threads=1" to pin the CPU '
                        "backend to one thread, or "
                        "--xla_force_host_platform_device_count=N for "
                        "multi-device runs")
    g.add_argument("--env", action="append", default=[], metavar="KEY=VAL",
                   help="set an environment variable before engine import "
                        "(repeatable), e.g. --env REPRO_DECODE_BACKEND="
                        "batched")


def apply(args: argparse.Namespace) -> None:
    """Install --env/--xla-flags and the compile-cache placement into
    os.environ.  Must run before any repro.core (hence jax) import to have
    any effect on XLA."""
    for spec in args.env:
        key, sep, val = spec.partition("=")
        if not sep or not key:
            raise SystemExit(f"--env wants KEY=VAL, got {spec!r}")
        os.environ[key] = val
    if args.xla_flags:
        prev = os.environ.get("XLA_FLAGS", "")
        os.environ["XLA_FLAGS"] = f"{prev} {args.xla_flags}".strip()
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", CACHE_DIR)
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
