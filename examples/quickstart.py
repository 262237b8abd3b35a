"""Quickstart: ingest a camera feed into the VideoStore engine, run
declarative scan queries, watch the storage manager adapt its tile layout
(paper §1's amber-alert flow) — and reopen the catalog from its manifest.

    PYTHONPATH=src python examples/quickstart.py
"""
import tempfile

import numpy as np

from repro.codec.encode import EncoderConfig
from repro.core import RegretPolicy, VideoStore
from repro.core.calibrate import calibrated_cost_model
from repro.data.video_gen import generate, sparse_spec

# 1. a "camera feed": procedural traffic video with ground-truth detections
spec = sparse_spec(seed=0, n_frames=128, height=192, width=320)
frames, detections = generate(spec)
print(f"video: {frames.shape}, objects: "
      f"{sorted({l for d in detections for l, _ in d})}")

# 2. a VideoStore catalog backed by disk, with the regret-based incremental
#    tiling policy (§4.4) for this camera
root = tempfile.mkdtemp(prefix="tasm_store_")
model = calibrated_cost_model(EncoderConfig(), seeds=(0,), repeats=1)
store = VideoStore(store_root=root)
store.add_video("traffic", encoder=EncoderConfig(gop=16, qp=8),
                policy=RegretPolicy(), cost_model=model)
store.ingest("traffic", frames)
print(f"ingested untiled: {store.storage_bytes('traffic') / 1e3:.0f} KB "
      f"-> catalog at {store.catalog_path}")

# 3. the query processor detects objects as a byproduct of queries and feeds
#    the semantic index via ADDMETADATA
for f, dets in enumerate(detections):
    for label, (y1, x1, y2, x2) in dets:
        store.add_metadata("traffic", f, label, x1, y1, x2, y2)
print("semantic index:", store.video("traffic").index.stats())

# 4. plan/execute split: EXPLAIN shows the SOTs/tiles the engine would
#    decode, with estimated cost from the what-if interface — no decoding
query = store.scan("traffic").labels("car").frames(0, 64)
print("\n" + query.explain().describe() + "\n")

# 5. ROI-restricted block decode (the default): a subframe scan decodes
#    only the 8x8 blocks its boxes intersect, so pixels_decoded tracks the
#    *requested* pixels, not tile area.  Toggle it off to see what the same
#    query costs under full-tile decode — results are bit-identical
store.roi_decode = False
full_px = query.execute().stats.pixels_decoded
store.tile_cache.clear()   # cold again, so the ROI run really decodes
store.roi_decode = True
roi_px = query.execute().stats.pixels_decoded
print(f"pixels decoded, full-tile {full_px / 1e6:.2f} M -> "
      f"ROI {roi_px / 1e6:.2f} M ({full_px / max(roi_px, 1):.1f}x fewer)")

# 5b. batched fused decode: VideoStore(decode=DecodeConfig(
#     backend="batched")) (or env REPRO_DECODE_BACKEND=batched, or
#     --decode-backend on tasm_serve.py) flattens every (tile, GOP,
#     block-mask) selection of a group fetch into one fused
#     dequant+IDCT+cumsum dispatch — Pallas on TPU, jitted XLA elsewhere —
#     instead of the per-tile numpy loop.  Decode counters are identical
#     and pixels agree within codec.batch.ORACLE_ATOL; bench/run.py
#     measures its speed on the chip (PERF.md)
from repro.core import DecodeConfig

batched = VideoStore(decode=DecodeConfig(backend="batched"))
batched.add_video("traffic", encoder=EncoderConfig(gop=16, qp=8))
batched.ingest("traffic", frames)
batched.add_detections("traffic", {f: d for f, d in enumerate(detections)})
r_batched = batched.scan("traffic").labels("car").frames(0, 64).execute()
r_numpy = query.execute()
same = all(a[:-1] == b[:-1] and np.array_equal(a[-1], b[-1])
           for a, b in zip(r_numpy.regions, r_batched.regions))
print(f"batched decode backend: {len(r_batched.regions)} regions, "
      f"bit-identical to numpy: {same}")
batched.close()

# 6. issue repeated declarative queries; the layout evolves under the policy
#    and the tile cache absorbs repeat decodes (epoch bumps invalidate it).
#    Tuning runs in the BACKGROUND by default: queries only emit workload
#    observations, the tuner thread re-tiles off the critical path, so
#    retile stays 0.0 ms for every query (pass tuning="inline" to get the
#    old synchronous behaviour)
for i in range(14):
    s = query.execute().stats
    print(f"q{i}: decode={s.decode_s * 1e3:6.1f} ms  "
          f"pixels={s.pixels_decoded / 1e6:5.2f} M  tiles={s.tiles_decoded:3.0f}"
          f"  cache={s.cache_hits}h/{s.cache_misses}m"
          f"  retile={s.retile_s * 1e3:6.1f} ms")

ts = store.drain_tuner()  # barrier: wait for background tuning to settle
print(f"tuner: {ts.observed} observations -> {ts.applied} retiles applied, "
      f"{ts.retile_s * 1e3:.0f} ms re-encode paid off the scan path")
print("final layouts:",
      [r.layout.describe() for r in store.video("traffic").store.sots])
print("\nafter adaptation:\n" + query.explain().describe())

# 6b. workload-predictive tile cache: the cache knobs now live on ONE
#     CacheConfig — byte budget, eviction ("reuse" weights entries by how
#     often they were re-accessed, "lru" is the legacy order), block
#     packing (ROI entries store only their 8x8 blocks, not a zero-padded
#     canvas), and prefetch.  The old VideoStore(tile_cache_bytes=...)
#     kwarg still works for one release as a deprecated alias.  With
#     prefetch on, the cache taps the tuner's workload log: after three
#     windows of a sliding scan it recognizes the monotone SOT progression
#     and decodes the NEXT SOTs on the worker pool before they are asked
#     for — later windows then decode zero tiles
from repro.core import CacheConfig

pred = VideoStore(cache=CacheConfig(prefetch=True, prefetch_depth=2))
pred.add_video("traffic", encoder=EncoderConfig(gop=16, qp=8), sot_len=16)
pred.ingest("traffic", frames)
pred.add_detections("traffic", {f: d for f, d in enumerate(detections)})
print()
for i in range(8):
    s = pred.scan("traffic").labels("car") \
            .frames(i * 16, (i + 1) * 16).execute().stats
    pred.drain_prefetch()  # barrier: the demo stays deterministic
    print(f"window {i}: pixels={s.pixels_decoded / 1e6:5.2f} M  "
          f"cache={s.cache_hits}h/{s.cache_misses}m")
cs = pred.tile_cache.stats()
print(f"prefetch: {cs.prefetch_issued} issued, {cs.prefetch_hits} hit, "
      f"{cs.prefetch_wasted} wasted; block packing saved "
      f"{cs.packed_bytes_saved / 1e6:.1f} MB of cache budget")
pred.close()

# 7. disjunctive predicate (one clause: car OR person), limited
res = store.scan("traffic").labels("car", "person").frames(0, 32) \
           .limit(50).execute()
print(f"\ndisjunctive query returned {len(res.regions)} regions (limit 50)")

# 8. verify pixels: the decoded crop matches the source (lossy codec)
f, box, px = res.regions[0]
y1, x1, y2, x2 = box
err = np.abs(px - frames[f, y1:y2, x1:x2]).mean()
print(f"mean |decoded - source| = {err:.2f} (8-bit scale)")

# 9. concurrent serving: overlapping scans submitted together merge their
#    SOT decodes (each shared tile decoded at most once, then cached)
with store.serve() as session:
    futs = [session.submit(store.scan("traffic").labels("car").frames(0, 64))
            for _ in range(4)]
    batch = [f.result() for f in futs]
hits = sum(r.stats.cache_hits for r in batch)
misses = sum(r.stats.cache_misses for r in batch)
print(f"\nserved 4 overlapping scans: {hits} cache hits, "
      f"{misses} fresh tile decodes")

# 10. reopen the catalog from its on-disk manifest: no re-ingest needed
reopened = VideoStore(store_root=root)
res2 = reopened.scan("traffic").labels("car").frames(0, 64).execute()
same = all(np.array_equal(p1, p2) for (_, _, p1), (_, _, p2)
           in zip(store.scan("traffic").labels("car").frames(0, 64)
                  .execute().regions, res2.regions))
print(f"reopened {reopened.videos()} from manifest; "
      f"scan bit-identical: {same}")

# 11. cross-process serving: expose the store over a socket and query it
#     with RemoteVideoStore — same declarative surface, shared cache, and
#     results bit-identical to in-process execute().  (In production the
#     server runs via `scripts/tasm_serve.py --socket ...` and clients are
#     separate processes; here both ends live in this script.)
import os

from repro.core import RemoteVideoStore, VideoStoreServer

sock = os.path.join(root, "tasm.sock")
with VideoStoreServer(reopened, path=sock, owns_store=False).start():
    with RemoteVideoStore(sock) as remote:
        r_remote = remote.scan("traffic").labels("car").frames(0, 64) \
                         .execute()
        same = all(np.array_equal(a[-1], b[-1])
                   for a, b in zip(res2.regions, r_remote.regions))
        print(f"\nremote scan over {remote.ping()['codec']} wire: "
              f"{len(r_remote.regions)} regions, bit-identical: {same}, "
              f"cache hits {r_remote.stats.cache_hits}")

# 12. distributed VideoStore: two nodes behind a ClusterRouter.  The router
#     places videos by consistent hash (persisted placement map), writes
#     every replica (replication=2 here), routes reads to the primary's
#     warm cache, and fails over if a node dies — all behind the SAME
#     declarative surface, bit-identical to a single store.  (In
#     production the nodes run `scripts/tasm_serve.py` and the router
#     `scripts/tasm_router.py`; here all three live in this script.)
from repro.core import (ClusterClient, ClusterRouter, ClusterRouterServer,
                        NoTilingPolicy)

nodes = {f"n{i}": os.path.join(root, f"node{i}.sock") for i in range(3)}
node_stores = {name: VideoStore() for name in nodes}
node_servers = {name: VideoStoreServer(node_stores[name], path=path,
                                       owns_store=False).start()
                for name, path in nodes.items()}
router = ClusterRouter(nodes, replication=2,
                       placement_path=os.path.join(root, "placement.json"))
router.add_video("traffic", encoder=EncoderConfig(gop=16, qp=8),
                 policy=NoTilingPolicy())
router.ingest("traffic", frames)
router.add_detections("traffic", {f: d for f, d in enumerate(detections)})
rsock = os.path.join(root, "router.sock")
with ClusterRouterServer(router, path=rsock, owns_store=False).start():
    with ClusterClient(rsock) as cluster:
        r_cluster = cluster.scan("traffic").labels("car").frames(0, 64) \
                           .execute()
        ref = VideoStore()
        ref.add_video("traffic", encoder=EncoderConfig(gop=16, qp=8),
                      policy=NoTilingPolicy())
        ref.ingest("traffic", frames)
        ref.add_detections("traffic", {f: d for f, d in enumerate(detections)})
        r_single = ref.scan("traffic").labels("car").frames(0, 64).execute()
        same = all(a[:-1] == b[:-1] and np.array_equal(a[-1], b[-1])
                   for a, b in zip(r_single.regions, r_cluster.regions))
        print(f"\ncluster of {len(nodes)} nodes (replication=2): "
              f"{len(r_cluster.regions)} regions, bit-identical to a "
              f"single store: {same}, placement "
              f"{cluster.placement()['assignments']}")

        # 12b. self-healing: kill the video's primary node for good, then
        #      one repair command re-replicates everything it held onto
        #      the spare node — tiles stream node→node in the background
        #      (checksummed, resumable, committed atomically), reads keep
        #      serving from the surviving replica throughout, and the
        #      placement flips only after the copy verifies.  (From a
        #      shell this is `tasm_router.py --socket ... --repair
        #      node=<name>`; the same RPCs drive it here.)
        victim = cluster.placement()["assignments"]["traffic"][0]
        node_servers.pop(victim).stop()
        node_stores.pop(victim).close()
        r_degraded = cluster.scan("traffic").labels("car").frames(0, 64) \
                            .execute()          # failover, no repair yet
        jobs = cluster.repair(node=victim)
        status = cluster.drain_repair()         # wait for the copy
        r_healed = cluster.scan("traffic").labels("car").frames(0, 64) \
                          .execute()
        same = all(a[:-1] == b[:-1] and np.array_equal(a[-1], b[-1])
                   for a, b in zip(r_single.regions, r_healed.regions))
        print(f"killed {victim} -> {len(r_degraded.regions)} regions via "
              f"failover; repair streamed {len(jobs)} job(s), "
              f"{status['stats']['chunks_copied']} chunks "
              f"({status['stats']['bytes_copied'] / 1e6:.2f} MB); healed "
              f"placement {cluster.placement()['assignments']['traffic']}, "
              f"bit-identical: {same}")
        ref.close()
router.close()
for srv in node_servers.values():
    srv.stop()
for s in node_stores.values():
    s.close()

# 13. zero-copy serving: on a same-host unix socket the server ships
#     result arrays through POSIX shared memory — clients map the pages
#     instead of copying them off the socket (transport="auto" negotiates
#     it; "socket" forces the npz fallback used for TCP/cross-host).
#     Both transports produce bit-identical bytes, and every reply's
#     marshalling cost is stamped into its ScanStats.
from repro.core.shm import shm_available

sock13 = os.path.join(root, "tasm13.sock")
with VideoStoreServer(reopened, path=sock13, owns_store=False).start():
    with RemoteVideoStore(sock13) as fast, \
         RemoteVideoStore(sock13, transport="socket") as slow:
        r_shm = fast.scan("traffic").labels("car").frames(0, 64).execute()
        r_npz = slow.scan("traffic").labels("car").frames(0, 64).execute()
        same = all(a[:-1] == b[:-1] and np.array_equal(a[-1], b[-1])
                   for a, b in zip(r_shm.regions, r_npz.regions))
        print(f"\nzero-copy serving (shm available: {shm_available()}): "
              f"negotiated {fast.transport!r} vs forced {slow.transport!r}, "
              f"bit-identical: {same}; "
              f"{r_shm.stats.payload_bytes} payload bytes marshalled in "
              f"{r_shm.stats.marshal_s * 1e3:.2f} ms over "
              f"{r_shm.stats.transport}")

reopened.close()
store.close()
