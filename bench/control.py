#!/usr/bin/env python3
"""The control of the pixel check, at a cell's own size.

    python bench/control.py --workload vr2k-select --seeds 1,2,3
    python bench/control.py --workload vr2k-select --seeds 1,2,3 \
        --in-program tpu_high --seconds 10

For each seed it makes the cell's archive and the requests a run would
check (the same sample of the same schedule), and reads the widest pixel
gap against the float64 reference of the answers decoded at lower
precisions: ``high`` (each float32 product as three bfloat16 products,
what XLA's ``Precision.HIGH`` does -- the step below the ``HIGHEST`` the
configurations state, which a later change would be tempted to take),
``bf16`` (one product) and ``f32``.  Where JAX sees a TPU, it also decodes
the same coefficients with ``jnp`` at ``Precision.HIGH`` and ``HIGHEST`` on
the chip.  A sound limit lies above what the program reads and below the
``high`` gap.

``--in-program P`` instead puts a decode at precision ``P`` in the place of
the program's fused decode (``codec.batch.decode_fused_op``) and drives
whole runs of the cell through ``run.run``: the server, the scheduler, the
cache, the window and the harness's own check.  Each seed prints the run's
``correct`` and ``checks``; the control has to read ``correct: false``.
``high`` and ``bf16`` decode on the host with the reference's arithmetic;
``tpu_high`` decodes on the chip with ``jnp`` at ``Precision.HIGH``.
Benchmark runs do not run this.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import sys

import numpy as np

import run

PRECISIONS = ("f32", "high", "bf16")


def requested_keys(spec: dict, cfg: dict, archives: dict, seed: int,
                   seconds: float) -> dict:
    """Camera -> the region keys a run of this seed would check: its
    sampled replies and, in a cluster cell, its read-back from each
    replica."""
    job, by_key = run.job_for(spec, cfg, seed, seconds)
    if job["loop"] == "open":
        qs = [by_key[json.dumps(i)] for i in job["sample"]]
    else:
        qs = [by_key[json.dumps([c, s])]
              for c, seqs in enumerate(job["sample"]) for s in seqs]
    if "cluster" in cfg:
        import cluster

        qs += cluster.read_back_queries(spec["mix"], cfg)
    keys: dict = {}
    for q in qs:
        cam = q.get("camera", 0)
        keys.setdefault(cam, set()).update(
            archives[cam].regions(q["label"], q["lo"], q["hi"]))
    return {cam: sorted(k) for cam, k in keys.items()}


def _jnp_decode(precision):
    """[F, M, 8, 8] int16 -> [F, M, 8, 8] float32 cumulative frames, with
    both contractions in ``jnp`` at ``precision``; jitted per qp."""
    import functools

    import jax
    import jax.numpy as jnp

    import reference

    d = jnp.asarray(reference.dct())

    @functools.partial(jax.jit, static_argnames="qp")
    def dec(q, qp):
        scale = jnp.concatenate([
            jnp.asarray(reference.quant(qp, True))[None],
            jnp.broadcast_to(jnp.asarray(reference.quant(qp, False)),
                             (q.shape[0] - 1, 8, 8))])
        c = q.astype(jnp.float32) * scale[:, None]
        x = jnp.einsum("ji,fnjk->fnik", d, c, precision=precision)
        x = jnp.einsum("fnik,kl->fnil", x, d, precision=precision)
        return jnp.cumsum(x, axis=0)

    return dec


def decode_op(precision: str):
    """A stand-in for the program's ``decode_fused_op`` that decodes at
    ``precision``: ``tpu_high`` with ``jnp`` at ``Precision.HIGH`` on the
    chip; any of ``reference.PRECISIONS`` on the host with the reference's
    arithmetic."""
    if precision == "tpu_high":
        import jax

        if jax.devices()[0].platform != "tpu":
            raise SystemExit("tpu_high needs a TPU: elsewhere XLA ignores "
                             "the precision of a float32 contraction")
        dec = _jnp_decode(jax.lax.Precision.HIGH)

        def op(q, *, qp, use_pallas=None, interpret=False):
            return dec(q, qp=qp)
    else:
        import reference

        def op(q, *, qp, use_pallas=None, interpret=False):
            q = np.asarray(q)
            return reference.decode(q[0], q[1:], qp, len(q), precision)
    return op


def tpu_gaps(arch, keys) -> dict:
    """Decode the coded blocks with jnp on the chip; widest gap by
    precision (empty where JAX sees no TPU)."""
    import jax

    import reference

    if jax.devices()[0].platform != "tpu":
        return {}
    out = {}
    for name, prec in (("tpu_high", jax.lax.Precision.HIGH),
                       ("tpu_highest", jax.lax.Precision.HIGHEST)):
        dec = _jnp_decode(prec)
        gap = 0.0
        for g, (col, kq, pq) in arch._coded.items():
            q = np.concatenate([kq[None], pq]).astype(np.int16)
            rec = np.asarray(dec(q, qp=arch.qp))
            ref = arch._decoded.get((g, "f64"))
            if ref is None:
                ref = reference.decode(kq, pq, arch.qp, len(pq) + 1)
            gap = max(gap, float(np.max(np.abs(rec - ref))))
        out[name] = gap
    return out


def in_program(args, precision: str) -> int:
    """Whole runs of the cell with the decode at ``precision`` in the
    program's place; one line per seed."""
    if "cluster" in run.load_cell(args.workload)["config"]:
        raise SystemExit("a cluster cell's nodes are their own processes: "
                         "plant the control with run.py --node-fault high")
    run.prepare_env(args.rehearse)
    import repro.codec.batch as batch

    batch.decode_fused_op = decode_op(precision)
    for seed in args.seeds.split(","):
        argv = ["--workload", args.workload, "--seed", seed, "--seconds",
                str(args.seconds), "--trace", "0"]
        out = io.StringIO()
        with contextlib.redirect_stdout(out):
            rc = run.run(run.parse_args(argv + ["--rehearse"] * bool(
                args.rehearse)))
        lines = out.getvalue().strip().splitlines()
        print("\n".join(lines[:-1]), file=sys.stderr, flush=True)
        line = json.loads(lines[-1]) if rc == 0 and lines else {}
        print(json.dumps({"workload": args.workload, "seed": int(seed),
                          "in_program": precision, "rc": rc,
                          "correct": line.get("correct"),
                          "checks": line.get("checks")}), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--in-program", choices=("high", "bf16", "tpu_high"),
                    help="drive whole runs with this decode in the "
                         "program's place")
    args = ap.parse_args(argv)
    if args.in_program:
        return in_program(args, args.in_program)
    spec = run.load_cell(args.workload)
    cfg = run.rehearsal_size(spec["config"]) if args.rehearse \
        else spec["config"]
    run.prepare_env(args.rehearse)
    import corpus
    import reference

    for seed in (int(s) % (1 << 64) for s in args.seeds.split(",")):
        if "cluster" in cfg:
            made = {c: corpus.generate(cfg, [seed, c])
                    for c in range(cfg["cluster"]["cameras"])}
        else:
            made = {0: corpus.generate(cfg, seed)}
        archives = {c: reference.Archive(frames, dets, cfg["gop"], cfg["qp"])
                    for c, (frames, dets) in made.items()}
        keys = requested_keys(spec, cfg, archives, seed, args.seconds)
        gaps = {p: 0.0 for p in PRECISIONS}
        for cam, ks in keys.items():
            arch = archives[cam]
            arch.prepare(ks)
            for f, box in ks:
                ref = arch.pixels(f, box)
                for p in PRECISIONS:
                    if ref.size:
                        gaps[p] = max(gaps[p], float(np.max(np.abs(
                            arch.pixels(f, box, p) - ref))))
            for p, g in tpu_gaps(arch, ks).items():
                gaps[p] = max(gaps.get(p, 0.0), g)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "regions": sum(map(len, keys.values())),
                          "gaps": gaps,
                          "limit": cfg["check"]["pixel_gap_limit"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
