"""Paper Experiment 2 (second environment): hop latency across transports.

The paper compares local-disk CMI cost against network+S3. Here, five ways
to move state between nodes:

``live``          direct device_put resharding (§Q5 on shared devices) —
                  both nodes share the process and device pool.
``store``         checkpoint → shared store → svc/hop restore (Fig. 3/4),
                  dest node in the *same* process.
``xproc``         the same store-mediated hop, but the destination node is a
                  real worker process behind the fabric RPC — save + socket
                  request + remote restore. The delta over ``store`` is the
                  fabric tax.
``stream``        the §Q5 streaming transport: chunks travel straight over
                  the fabric socket (``repro.fabric.stream``), never
                  touching the disk. On this host that also sidesteps the
                  9p filesystem entirely.
``stream_delta``  a repeat stream hop after mutating ``mutate_frac`` of the
                  rows: only changed chunks travel (hash delta against the
                  receiver's cached baseline).

Plus the composed experiment — a 3-node, 3-stage remote itinerary (Fig. 8:
read on W, compute on W2, write on W3, product back to the driver):

``tour_stream``   every leg streamed: hop in, worker-initiated relays
                  between stages (svc/relay), streamed fetch back — the
                  store is never touched.
``tour_store``    the same tour with ``via="store"``: each leg is a
                  checkpoint -> shared store -> restore round-trip. The
                  ratio is the end-to-end cost of store-chaining a tour.

Trials are interleaved across configs (config A trial 1, config B trial 1,
..., config A trial 2, ...) so filesystem cache state and background noise
spread evenly instead of biasing whichever config runs last.

Standalone run records machine-readable results (schema mirrors
``BENCH_ckpt.json``)::

    PYTHONPATH=src python -m benchmarks.bench_hop --mb 64 --out BENCH_hop.json
    PYTHONPATH=src python -m benchmarks.bench_hop --smoke   # CI regression run
"""

from __future__ import annotations

import json
import os
import shutil
import statistics
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import DHP, NBS
from repro.launch.mesh import auto_mesh
from repro.utils import tree_nbytes

MB = 1 << 20

ENV_NOTES = (
    "2-vCPU gVisor sandbox over 9p: store-mediated hops pay serialize + fsync "
    "+ COMMIT + re-read through an anti-scaling network filesystem; the stream "
    "path moves the same chunks over a unix socket (memory to memory) with "
    "hashing pipelined against the send, so its win here combines transport "
    "and filesystem avoidance. Delta hops resend only chunks whose blake2b "
    "changed vs the receiver's cached baseline. The tour configs chain a "
    "3-stage remote itinerary across 3 worker processes: tour_stream keeps "
    "every leg on the wire (hop in, svc/relay node-to-node, streamed fetch "
    "back -- the store is never touched); tour_store checkpoints/restores "
    "through the shared store on every leg."
)


def bench(
    n_mb: int = 64,
    trials: int = 3,
    xproc: bool = True,
    chunk_mb: int = 4,
    mutate_frac: float = 0.25,
    strict_stream: bool = False,
) -> tuple[list[tuple[str, float, str]], dict]:
    """Run the hop matrix. Returns ``(csv rows, json-able results dict)``.

    A transparent stream→store fallback (which ``dhp.hop`` is designed to
    absorb) drops that trial's stream timing and is counted in
    ``results["stream_fallbacks"]``; with ``strict_stream`` (the CI smoke
    contract) it raises instead.
    """
    rng = np.random.default_rng(0)
    n = n_mb * MB // 4 // 256
    make_state = lambda: {"x": jnp.asarray(rng.standard_normal((n, 256)), jnp.float32)}  # noqa: E731
    nbytes = tree_nbytes(make_state())
    tour_n = max(1, n // 2)  # tour state is float64: halve rows for equal MB
    chunk_bytes = chunk_mb * MB
    root = tempfile.mkdtemp(prefix="bench-hop-")
    sup = None
    times: dict[str, list[float]] = {"hop_live": [], "hop_store": []}
    stream_stats: dict = {}
    comp_stats: dict = {}
    stream_fallbacks = 0
    tour_fallbacks = 0
    try:
        nbs = NBS(root)
        mesh = auto_mesh((1,), ("data",))
        nbs.add_node("A", mesh=mesh)
        nbs.add_node("B", mesh=mesh)
        nbs.add_node("C", mesh=None)  # store-hop dest (no mesh -> store path)
        hop_vias: list[str] = []  # per-tour transport log (fallback detection)
        nbs.plugins.subscribe("on_hop", lambda **kw: hop_vias.append(kw["via"]))
        if xproc:
            try:
                from repro.fabric.supervisor import FabricSupervisor

                sup = FabricSupervisor(root)
                handle = sup.spawn("W", serve_only=True)
                nbs.add_remote_node("W", handle.address)
                times["hop_xproc"] = []
                times["hop_stream"] = []
                times["hop_stream_delta"] = []
                times["hop_stream_zstd"] = []
                times["hop_stream_raw"] = []
                # two more workers for the 3-node remote tour
                for wname in ("W2", "W3"):
                    nbs.add_remote_node(wname, sup.spawn(wname, serve_only=True).address)
                times["tour_stream"] = []
                times["tour_store"] = []
            except Exception as e:  # pragma: no cover - spawn-impossible envs
                print(f"xproc mode unavailable ({e}); skipping")
                sup = None
        # interleaved: one trial of every config per round
        for _ in range(trials):
            dhp = DHP(nbs, "A", chunk_bytes=chunk_bytes)
            state = make_state()
            t0 = time.perf_counter()
            state = dhp.hop(state, "B", via="live")
            jax.block_until_ready(state)
            times["hop_live"].append(time.perf_counter() - t0)
            del state

            dhp = DHP(nbs, "A", chunk_bytes=chunk_bytes)
            state = make_state()
            t0 = time.perf_counter()
            state = dhp.hop(state, "C", via="store")
            jax.block_until_ready(state)
            times["hop_store"].append(time.perf_counter() - t0)
            del state

            if "hop_xproc" in times:
                dhp = DHP(nbs, "A", chunk_bytes=chunk_bytes)
                state = make_state()
                t0 = time.perf_counter()
                ref = dhp.hop(state, "W", via="store")
                times["hop_xproc"].append(time.perf_counter() - t0)
                nbs.call("W", "svc/drop", token=ref.token)

            if "hop_stream" in times:
                wnode = nbs.node("W")
                dhp = DHP(nbs, "A", chunk_bytes=chunk_bytes)
                state = make_state()
                host = np.asarray(state["x"])
                t0 = time.perf_counter()
                ref = dhp.hop(state, "W", via="auto")
                dt_full = time.perf_counter() - t0
                if ref.via == "stream":
                    times["hop_stream"].append(dt_full)
                else:  # transparent fallback: not a stream timing
                    if strict_stream:
                        raise RuntimeError(f"stream hop fell back: {ref}")
                    stream_fallbacks += 1

                # repeat hop with mutate_frac of the rows changed: the
                # receiver still holds the baseline, so only changed chunks
                # should travel
                mutated = host.copy()
                mutated[: max(1, int(n * mutate_frac))] += 1.0
                state2 = {"x": jnp.asarray(mutated)}
                t0 = time.perf_counter()
                ref2 = dhp.hop(state2, "W", via="auto")
                dt_delta = time.perf_counter() - t0
                if ref2.via == "stream" and ref.via == "stream":
                    times["hop_stream_delta"].append(dt_delta)
                    receipt = wnode.last_stream_receipt or {}
                    stream_stats = {
                        "chunks": receipt.get("chunks"),
                        "delta_data_chunks": receipt.get("data_chunks"),
                        "delta_ref_chunks": receipt.get("ref_chunks"),
                        "delta_sent_bytes": receipt.get("sent_bytes"),
                        "mutate_frac": mutate_frac,
                    }
                elif strict_stream:
                    raise RuntimeError(f"delta hop fell back: {ref2}")
                else:
                    stream_fallbacks += 1
                nbs.call("W", "svc/drop", token=ref.token)  # baseline state
                nbs.call("W", "svc/drop", token=ref2.token)
                wnode._stream_baseline = None  # next round streams full
                del state, state2

            if "hop_stream_zstd" in times:
                # compressed vs raw wire on compressible-but-unique state
                # (small-int floats: every chunk distinct, high redundancy —
                # dedup can't shortcut it, only the codec can). The config
                # name says zstd; the ladder negotiates the best codec both
                # ends speak (zstd > lz4 > zlib stdlib floor).
                from repro.fabric import wire as fabwire

                comp_np = rng.integers(0, 8, (n, 256)).astype(np.float32)
                wnode = nbs.node("W")
                # explicit opt-in: the sender only offers fast codecs by
                # default, so name the best codec this build can speak
                # (receivers always answer with their full speakable set)
                best = (fabwire.speakable_codecs() or ("zlib",))[0]
                for cfg, env in (("hop_stream_zstd", best), ("hop_stream_raw", "off")):
                    old_env = os.environ.pop(fabwire.COMPRESSION_ENV, None)
                    if env is not None:
                        os.environ[fabwire.COMPRESSION_ENV] = env
                    try:
                        dhp = DHP(nbs, "A", chunk_bytes=chunk_bytes)
                        state = {"x": jnp.asarray(comp_np)}
                        t0 = time.perf_counter()
                        ref = dhp.hop(state, "W", via="auto")
                        dt = time.perf_counter() - t0
                        if ref.via == "stream":
                            times[cfg].append(dt)
                            receipt = wnode.last_stream_receipt or {}
                            comp_stats[cfg] = {
                                "sent_bytes": receipt.get("sent_bytes"),
                                "chunks": receipt.get("chunks"),
                            }
                        elif strict_stream:
                            raise RuntimeError(f"{cfg} hop fell back: {ref}")
                        else:
                            stream_fallbacks += 1
                        nbs.call("W", "svc/drop", token=ref.token)
                        wnode._stream_baseline = None
                        del state
                    finally:
                        if old_env is not None:
                            os.environ[fabwire.COMPRESSION_ENV] = old_env
                        else:
                            os.environ.pop(fabwire.COMPRESSION_ENV, None)
                comp_stats["codec"] = best

            if "tour_stream" in times:
                # the 3-stage remote itinerary, stream-chained vs store-chained
                # on the SAME input (bit-identical products double as a check)
                from repro.core.itinerary import Itinerary, Stage
                from repro.fabric import worker as fabworker

                stages = [
                    Stage("W", fabworker.tour_read, "read"),
                    Stage("W2", fabworker.tour_compute, "compute"),
                    Stage("W3", fabworker.tour_write, "write"),
                ]
                base = rng.standard_normal((tour_n, 256))
                outs = {}
                for cfg, via in (("tour_stream", "auto"), ("tour_store", "store")):
                    dhp = DHP(nbs, "A", chunk_bytes=chunk_bytes)
                    hop_vias.clear()
                    t0 = time.perf_counter()
                    outs[cfg] = Itinerary(dhp, via=via).run({"x": base.copy()}, stages)
                    dt = time.perf_counter() - t0
                    # "store" = a hop/relay leg fell back; "fetch_store" = the
                    # streamed return leg did. Either disqualifies the timing.
                    if via == "auto" and any("store" in v for v in hop_vias):
                        if strict_stream:
                            raise RuntimeError(f"tour leg fell back: {hop_vias}")
                        tour_fallbacks += 1
                    else:
                        times[cfg].append(dt)
                if outs["tour_stream"]["x"].tobytes() != outs["tour_store"]["x"].tobytes():
                    raise RuntimeError("tour products differ across transports")
                del outs
    finally:
        if sup is not None:
            sup.shutdown()
        shutil.rmtree(root, ignore_errors=True)

    results: dict = {
        "state_bytes": nbytes,
        "chunk_bytes": chunk_bytes,
        "trials": trials,
        "env": {
            "cpu_count": os.cpu_count(),
            "tmpdir": tempfile.gettempdir(),
            "notes": ENV_NOTES,
        },
        "configs": {},
        "stream_fallbacks": stream_fallbacks,
        "tour_fallbacks": tour_fallbacks,
        "tour": {"stages": 3, "nodes": ["W", "W2", "W3"],
                 "state_bytes": tour_n * 256 * 8},
    }
    t_live = statistics.median(times["hop_live"])
    rows = [("hop_live", t_live * 1e6, f"{nbytes/t_live/1e9:.2f}GB/s")]
    for key in ("hop_store", "hop_xproc", "hop_stream", "hop_stream_delta",
                "hop_stream_zstd", "hop_stream_raw", "tour_stream", "tour_store"):
        if key not in times or not times[key]:
            continue
        t = statistics.median(times[key])
        rows.append(
            (key, t * 1e6,
             f"{nbytes/t/1e9:.2f}GB/s vs_live={t/max(t_live,1e-9):.1f}x")
        )
    for key, ts in times.items():
        if not ts:
            continue
        t = statistics.median(ts)
        results["configs"][key] = {
            "median_s": t,
            "gbps": nbytes / t / 1e9,
            "trials_s": ts,
        }
    cfg = results["configs"]
    ratios = {}
    if "hop_stream" in cfg:
        if "hop_store" in cfg:
            ratios["store_over_stream"] = cfg["hop_store"]["median_s"] / cfg["hop_stream"]["median_s"]
        if "hop_xproc" in cfg:
            ratios["xproc_over_stream"] = cfg["hop_xproc"]["median_s"] / cfg["hop_stream"]["median_s"]
        if "hop_stream_delta" in cfg:
            ratios["stream_over_delta"] = (
                cfg["hop_stream"]["median_s"] / cfg["hop_stream_delta"]["median_s"]
            )
    if "hop_stream_zstd" in cfg and "hop_stream_raw" in cfg:
        ratios["raw_over_compressed_time"] = (
            cfg["hop_stream_raw"]["median_s"] / cfg["hop_stream_zstd"]["median_s"]
        )
        zb = (comp_stats.get("hop_stream_zstd") or {}).get("sent_bytes")
        rb = (comp_stats.get("hop_stream_raw") or {}).get("sent_bytes")
        if zb and rb:
            ratios["compressed_over_raw_bytes"] = zb / rb
    if "tour_stream" in cfg and "tour_store" in cfg:
        ratios["tour_store_over_stream"] = (
            cfg["tour_store"]["median_s"] / cfg["tour_stream"]["median_s"]
        )
    results["ratios"] = ratios
    results["stream"] = stream_stats
    results["compression"] = comp_stats
    return rows, results


def run(n_mb: int = 64, trials: int = 3, xproc: bool = True) -> list[tuple[str, float, str]]:
    rows, _ = bench(n_mb=n_mb, trials=trials, xproc=xproc)
    return rows


def main(argv: list[str] | None = None) -> None:
    import argparse

    ap = argparse.ArgumentParser(description="hop transport benchmark")
    ap.add_argument("--mb", type=int, default=64, help="state size (MB)")
    ap.add_argument("--trials", type=int, default=3)
    ap.add_argument("--chunk-mb", type=int, default=4)
    ap.add_argument("--mutate-frac", type=float, default=0.25)
    ap.add_argument("--no-xproc", action="store_true")
    ap.add_argument(
        "--smoke", action="store_true",
        help="tiny state, 1 trial: regression-checks the transports without "
        "timing flakiness (CI)",
    )
    ap.add_argument("--out", default=None, help="write JSON results here")
    args = ap.parse_args(argv)
    if args.smoke:
        args.mb, args.trials, args.chunk_mb = 8, 1, 1

    rows, results = bench(
        n_mb=args.mb, trials=args.trials, xproc=not args.no_xproc,
        chunk_mb=args.chunk_mb, mutate_frac=args.mutate_frac,
        strict_stream=args.smoke,
    )
    print(f"{'config':>18} {'median ms':>10} {'GB/s':>7}")
    for name, r in results["configs"].items():
        print(f"{name:>18} {r['median_s']*1e3:>10.1f} {r['gbps']:>7.2f}")
    for k, v in results["ratios"].items():
        print(f"{k}: {v:.2f}x")
    if args.smoke:
        # the smoke contract: stream, delta, and the stream-chained remote
        # tour all ran end to end without ever falling back to the store
        for need in ("hop_stream", "hop_stream_delta", "hop_stream_zstd",
                     "hop_stream_raw", "tour_stream", "tour_store"):
            if need not in results["configs"]:
                raise SystemExit(f"smoke: {need} did not run")
        print("smoke ok: stream, delta, and tour transports ran without fallback")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1, sort_keys=True)
        print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
