"""The one traffic generator: a mix file of parameters -> requests.

A mix (``bench/traffic/<name>.json``) has three parts.

``arrivals`` -- how requests are offered:
  ``{"kind": "poisson", "rate_per_s": r}``  open loop, exponential gaps;
  ``{"kind": "closed", "clients": n}``  n clients, each sending its next
  request when the previous reply arrives.

``queries`` -- what is asked:
  ``{"kind": "ranges", "labels": {label: share}, "lengths": [...]}``
  selections of a label over a frame range of one of the lengths, starting
  anywhere it fits;
  ``{"kind": "catalogue", "size": k, "zipf_s": s, "labels": {...},
  "length_range": [a, b]}``  k (label, range) queries, asked with Zipf(s)
  popularity (in a closed loop, in an order whose every stretch holds the
  ranks in their Zipf shares: smooth weighted round robin, the clients
  taking turns along it); served by a cluster configuration of c
  ``cameras``, entry i asks camera ``i mod c`` (a request's ``camera``), so
  a round robin over the cameras;
  ``{"kind": "gop_cycle", "label": tag}``  whole GOPs of the whole-frame
  tag, each client cycling over the archive from its own starting GOP.
  Every query has a ``class`` ("sel" or "scan") that metrics filter on.

``sample`` -- how many answers a run checks against the reference (open
loop: requests; closed loop: replies per client).  ``warm_fill: n`` asks
the n most popular catalogue queries once during set-up.

So that seeds change the order of the work and not its amount, every seed
gets the same multiset of inter-arrival gaps (quantiles of the
exponential), of (label, length) pairs (equal counts of each length, each
with the labels in their exact shares) and of popularity counts, with the
same label and length at each popularity rank; the seed deals them out.
Only where a range starts within the archive is drawn freely.
"""
from __future__ import annotations

import json
import math
import pathlib

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent


def load(name: str) -> dict:
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


def _exact_counts(shares: dict, n: int) -> list[str]:
    """n labels in exactly the given shares (largest remainder)."""
    keys = sorted(shares)
    total = sum(shares.values())
    raw = [shares[k] / total * n for k in keys]
    base = [int(math.floor(r)) for r in raw]
    order = sorted(range(len(keys)), key=lambda i: raw[i] - base[i],
                   reverse=True)
    for i in order[:n - sum(base)]:
        base[i] += 1
    return [k for k, c in zip(keys, base) for _ in range(c)]


def _gaps(rate: float, n: int, rng) -> np.ndarray:
    """n exponential gaps of mean 1/rate: their quantiles, dealt out."""
    q = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    return rng.permutation(q)


def _arrival_times(arr: dict, seconds: float, rng) -> np.ndarray:
    rate = float(arr["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    if arr["kind"] == "poisson":
        return np.cumsum(_gaps(rate, n, rng))
    raise ValueError(f"unknown open-loop arrivals {arr['kind']!r}")


def longest(q: dict) -> int:
    """Frames in the longest range a selection mix asks for."""
    if q["kind"] == "ranges":
        return int(max(q["lengths"]))
    if q["kind"] == "catalogue":
        return int(q["length_range"][1])
    raise ValueError(f"queries {q['kind']!r} are not selections")


def _ranges(q: dict, n: int, n_frames: int, rng) -> list[dict]:
    lengths = [int(min(q["lengths"][i % len(q["lengths"])], n_frames))
               for i in range(n)]
    # every length gets the labels in their exact shares, so the multiset
    # of (label, length) pairs is the same on every seed
    pairs = []
    for length in sorted(set(lengths)):
        k = lengths.count(length)
        pairs += [(str(lab), length) for lab in _exact_counts(q["labels"], k)]
    out = []
    for j in rng.permutation(len(pairs)):
        label, length = pairs[j]
        lo = int(rng.integers(0, n_frames - length + 1))
        out.append({"label": label, "lo": lo, "hi": lo + length})
    return out


def catalogue(q: dict, cfg: dict, seed: int) -> list[dict]:
    """The k (label, range) queries of a catalogue mix over the archive of
    configuration ``cfg``, most popular first.  Which label and length
    each popularity rank has is fixed (dealt by a constant stream, so every
    seed asks the same amount of work); the seed draws where each range
    starts.  Where ``cfg`` is a cluster of c cameras, entry i asks camera
    i mod c."""
    n_frames = cfg["n_frames"]
    fixed = np.random.default_rng(0)
    k = int(q["size"])
    a, b = q["length_range"]
    lengths = fixed.permutation(np.round(np.linspace(
        a, min(b, n_frames), k)).astype(int))
    labels = fixed.permutation(_exact_counts(q["labels"], k))
    rng = np.random.default_rng([seed, 1])
    cameras = cfg.get("cluster", {}).get("cameras", 0)
    out = []
    for i, (label, length) in enumerate(zip(labels, lengths)):
        lo = int(rng.integers(0, n_frames - length + 1))
        out.append({"label": str(label), "lo": lo, "hi": lo + int(length)})
        if cameras:
            out[-1]["camera"] = i % cameras
    return out


def _zipf_counts(k: int, s: float, n: int) -> np.ndarray:
    p = 1.0 / np.arange(1, k + 1) ** s
    raw = p / p.sum() * n
    base = np.floor(raw).astype(int)
    for i in np.argsort(base - raw)[:n - base.sum()]:
        base[i] += 1
    return base


def open_loop(mix: dict, cfg: dict, seed: int, seconds: float) -> list[dict]:
    """Requests due in ``seconds``, in order: ``{"i", "due", "cls",
    "label", "lo", "hi"}`` with ``due`` in seconds from the window's
    start."""
    rng = np.random.default_rng([seed, 0])
    due = _arrival_times(mix["arrivals"], seconds, rng)
    due = due[due < seconds]
    q = mix["queries"]
    n = len(due)
    if q["kind"] == "ranges":
        asks = _ranges(q, n, cfg["n_frames"], rng)
    elif q["kind"] == "catalogue":
        cat = catalogue(q, cfg, seed)
        counts = _zipf_counts(len(cat), float(q["zipf_s"]), n)
        asks = [cat[i] for i in rng.permutation(np.repeat(
            np.arange(len(cat)), counts))]
    else:
        raise ValueError(f"queries {q['kind']!r} need a closed loop")
    return [dict(a, i=i, due=float(t), cls=q["class"])
            for i, (t, a) in enumerate(zip(due, asks))]


def _zipf_order(k: int, s: float, n: int) -> list[int]:
    """n popularity ranks (0-based) by smooth weighted round robin over the
    Zipf(s) weights of k ranks: every stretch of the sequence holds each
    rank close to its share."""
    w = 1.0 / np.arange(1, k + 1) ** s
    cur = np.zeros(k)
    out = []
    for _ in range(n):
        cur += w
        i = int(np.argmax(cur))
        cur[i] -= w.sum()
        out.append(i)
    return out


def closed_loop(mix: dict, cfg: dict, seed: int, per_client: int
                ) -> list[list[dict]]:
    """Each client's queries in the order it sends them."""
    q = mix["queries"]
    n_clients = int(mix["arrivals"]["clients"])
    if q["kind"] == "catalogue":
        cat = catalogue(q, cfg, seed)
        order = _zipf_order(len(cat), float(q["zipf_s"]),
                            per_client * n_clients)
        return [[dict(cat[i], cls=q["class"]) for i in order[c::n_clients]]
                for c in range(n_clients)]
    if q["kind"] != "gop_cycle":
        raise ValueError(f"queries {q['kind']!r} need an open loop")
    n_gops = cfg["n_frames"] // cfg["gop"]
    first = int(np.random.default_rng([seed, 0]).integers(0, n_gops))
    out = []
    for c in range(n_clients):
        g0 = first + c * n_gops // n_clients
        out.append([{"cls": q["class"], "label": q["label"],
                     "lo": ((g0 + j) % n_gops) * cfg["gop"],
                     "hi": ((g0 + j) % n_gops + 1) * cfg["gop"]}
                    for j in range(per_client)])
    return out


def open_sample(requests: list[dict], k: int, seed: int) -> list[int]:
    """Indices of the requests whose answers a run checks: the longest
    request (first among equals) and k-1 more drawn from the seed."""
    if not requests:
        return []
    longest = max(range(len(requests)),
                  key=lambda i: requests[i]["hi"] - requests[i]["lo"])
    rest = [i for i in range(len(requests)) if i != longest]
    rng = np.random.default_rng([seed, 2])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return sorted([longest] + [rest[i] for i in pick])


#: a closed-loop client's replies that a run may check: its first ones,
#: which every client finishes inside any window
CLOSED_SAMPLE_AMONG = 12


def closed_sample(n_clients: int, k: int, seed: int) -> list[list[int]]:
    """Per client, the sequence numbers of the replies a run checks: k
    among its first ``CLOSED_SAMPLE_AMONG``."""
    rng = np.random.default_rng([seed, 2])
    m = CLOSED_SAMPLE_AMONG
    return [sorted(rng.choice(m, size=min(k, m), replace=False).tolist())
            for _ in range(n_clients)]
