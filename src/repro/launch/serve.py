"""Serving driver: prefill + batched decode with the KV-cache engine.

``python -m repro.launch.serve --arch qwen3-4b --reduced --tokens 32``
runs prompt prefill then greedy decode for a batch of requests,
reporting per-token latency. The same entry point drives the full
configs on a production mesh (decode cells of the dry-run prove those
shardings compile).

``--monitor-every K`` attaches a **pipelined in-situ chain** to the
request loop (stats → FFT → bandpass on the last-token logits, host
writer at the tail): every K decode steps a logits snapshot is
*submitted to an* :class:`~repro.serve.fft_engine.FFTServeEngine`
monitor bucket, and the engine coalesces ``--monitor-batch`` snapshots
into ONE batched field handed to the chain — *in-flight batching*: the
decode loop never blocks on the monitor (the chain's device stages
ride async dispatch, the host writer runs on the pipeline worker, and
the engine's bounded admission backpressures only if analysis falls
far behind). The trailing partial batch goes through the same
``engine.flush()`` path as the in-loop submits — there is exactly one
flush code path. The report gains the chain's overlap-efficiency
numbers plus the engine's coalescing/queue accounting, and is emitted
as BENCH rows (``--bench-out``, trend-gateable) rather than a bare
JSON dump.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import registry
from repro.core.fft import plan as plan_mod
from repro.launch.mesh import make_host_mesh
from repro.models import lm
from repro.runtime.cluster import (add_cluster_args, config_from_args,
                                   init_cluster)
from repro.sharding.policy import make_policy


def _build_monitor(args, cfg, bridge=None):
    """The pipelined in-situ chain the decode loop feeds: one batched
    field of ``--monitor-batch`` stacked logit snapshots per submit.
    Warmed on zeros before returning — trace/compile and the chain's
    device-probe calibration must not land inside the timed decode
    loop. With ``bridge`` (the M→N in-transit split) the warm-up also
    rides the bridge, so the analysis chain compiles against
    consumer-mesh inputs from the first real submit."""
    from pathlib import Path

    from repro.core.insitu.bridge import BridgeData, GridMeta
    from repro.core.insitu.config import build_chain

    chain = build_chain({
        "mode": "pipelined",
        "chain": [
            {"endpoint": "stats", "array": "field"},
            {"endpoint": "fft", "array": "field", "direction": "forward",
             "local": True, "batch_ndim": 1},
            {"endpoint": "bandpass", "array": "field", "keep_frac": 0.25},
            {"endpoint": "writer", "array": "insitu_stats",
             "out_dir": args.monitor_dir, "prefix": "logit_stats"},
        ],
    }, mesh=None, grid=GridMeta((args.batch, cfg.vocab_size)))
    warm = BridgeData(
        arrays={"field": jnp.zeros(
            (args.monitor_batch, args.batch, cfg.vocab_size),
            jnp.float32)},
        step=0, meta={"primary": "field"})
    if bridge is not None:
        # send() is collective — every process calls it — but only
        # consumer participants receive the arrays (host transport
        # hands producers None leaves), so only they warm the chain
        warm = bridge.send(warm)
        if not bridge.is_consumer():
            bridge.reset_stats()  # warm-up must not skew the report
            return chain
    chain.execute(warm)           # compile the fused device program
    chain.execute(warm)           # consume the device-probe block
    chain.drain()
    chain.reset_stats()
    if bridge is not None:
        bridge.reset_stats()      # warm-up must not skew the report
    writer = chain.endpoints[-1]  # drop the warm-up artifacts
    for f in writer.written:
        Path(f).unlink(missing_ok=True)
    writer.written.clear()
    return chain


def _attach_monitor_engine(args, chain, bridge=None):
    """Wire the chain behind an :class:`FFTServeEngine` monitor bucket:
    the decode loop submits raw in-flight snapshots; the engine
    coalesces ``--monitor-batch`` of them into one stacked BridgeData
    per chain execute. Returns the engine (manual tick mode — the
    driver steps it, keeping ``chain.execute`` on the decode thread
    inside the active mesh context)."""
    from repro.core.insitu.bridge import BridgeData
    from repro.serve.fft_engine import FFTServeEngine

    def execute_batch(payloads, step_idx):
        field = jnp.stack(list(payloads))
        payload = BridgeData(arrays={"field": field}, step=step_idx,
                             meta={"primary": "field"})
        if bridge is not None:
            payload = bridge.send(payload)
            if not bridge.is_consumer():
                return None       # producers hold None leaves, no chain
        chain.execute(payload)
        return None

    engine = FFTServeEngine(max_pending=4 * args.monitor_batch,
                            linger_s=float("inf"))  # flush-at only
    engine.register_bucket("monitor", execute_batch,
                           flush_at=args.monitor_batch)
    return engine


def _emit_report_rows(report: dict, path: str) -> None:
    """End-of-run report as BENCH rows (the trend-gateable schema of
    ``benchmarks/run.py``) instead of a bare JSON print: one row per
    headline latency, the full report under ``derived``."""
    from pathlib import Path

    rows = {
        "serve_run_prefill": {
            "us_per_call": round(report["prefill_ms"] * 1e3, 1),
            "derived": f"batch={report['batch']}"},
        "serve_run_decode_token": {
            "us_per_call": round(report["decode_ms_per_token"] * 1e3, 1),
            "derived": f"tokens_per_s={report['tokens_per_s']}"},
    }
    if "monitor" in report:
        mon = report["monitor"]
        rows["serve_run_monitor_submit"] = {
            "us_per_call": round(mon["engine"]["submit_us_p50"], 1),
            "derived": (f"submits={mon['submits']} "
                        f"coalesced={mon['snapshots']}->"
                        f"{mon['submits']}")}
    out = Path(path)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(
        {"rows": rows, "unit": "us_per_call",
         "source": "repro.launch.serve", "report": report},
        indent=2, sort_keys=True) + "\n")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--monitor-every", type=int, default=0,
                    help="attach the pipelined in-situ logits monitor "
                         "every K decode steps (0 = off)")
    ap.add_argument("--monitor-batch", type=int, default=4,
                    help="snapshots batched into one in-flight submit")
    ap.add_argument("--monitor-dir", default="results/serve_monitor")
    ap.add_argument("--bench-out", default="results/BENCH_serve_run.json",
                    help="end-of-run report lands here as BENCH rows "
                         "(trend_check-compatible; '' disables)")
    ap.add_argument("--wisdom", default=None, metavar="FILE",
                    help="persistent autotune wisdom file: measured "
                         "sweep winners are read at bring-up and new "
                         "ones persisted, so restarts skip the timed "
                         "sweeps (overrides REPRO_WISDOM_FILE; "
                         "docs/wisdom.md)")
    ap.add_argument("--wisdom-mode", default="readwrite",
                    choices=("off", "read", "readwrite"),
                    help="read = consult wisdom but never write it")
    ap.add_argument("--transit-consumers", type=int, default=0,
                    metavar="N",
                    help="in-transit M→N split: decode on all but the "
                         "last N devices and run the logits monitor on "
                         "a disjoint N-device consumer mesh (0 = "
                         "analyze in place). Multi-process clusters: "
                         "every process must keep at least one decode "
                         "device or the run aborts (docs/multihost.md, "
                         "subset collectives)")
    ap.add_argument("--elastic", action="store_true",
                    help="put the monitor's consumer mesh under an "
                         "ElasticController: consumer ranks heartbeat "
                         "at monitor cadence and a rank missing its "
                         "lease is rescaled away without restarting "
                         "decode (docs/elastic.md; requires "
                         "--transit-consumers)")
    ap.add_argument("--elastic-lease", type=float, default=30.0,
                    metavar="SECONDS",
                    help="heartbeat lease; a consumer rank missing 3 "
                         "leases is declared dead")
    add_cluster_args(ap)
    args = ap.parse_args(argv)
    if args.wisdom:
        # before any measured planning (restarts warm-start from it)
        plan_mod.set_wisdom(args.wisdom, args.wisdom_mode)
    # multi-process bring-up (env/flag-driven; single-process no-op)
    init_cluster(config_from_args(args))

    cfg = (registry.get_reduced(args.arch) if args.reduced
           else registry.get_config(args.arch))
    assert cfg.family != "encdec", "use whisper serve example for enc-dec"
    transit_bridge = None
    elastic = None
    if args.transit_consumers:
        # M→N in-transit: decode on the producer mesh, monitor on the
        # disjoint consumer mesh
        if args.elastic:
            # the controller duck-types the bridge: monitor warm-up and
            # every engine submit route to the newest generation's mesh
            from repro.launch.mesh import make_elastic_setup
            mesh, elastic = make_elastic_setup(
                args.transit_consumers, noun="decode",
                lease=args.elastic_lease)
            transit_bridge = elastic
        else:
            from repro.launch.mesh import make_transit_setup
            mesh, transit_bridge = make_transit_setup(
                args.transit_consumers, noun="decode")
    elif args.elastic:
        raise SystemExit("--elastic requires --transit-consumers N "
                         "(there is no consumer mesh to rescale)")
    else:
        mesh = make_host_mesh()
    policy = make_policy(mesh, global_batch=args.batch)

    key = jax.random.PRNGKey(args.seed)
    params = lm.init_params(cfg, key, jnp.float32)
    cache_len = args.prompt_len + args.tokens

    prompts = jax.random.randint(key, (args.batch, args.prompt_len), 0,
                                 cfg.vocab_size)

    prefill = jax.jit(lambda p, b: lm.prefill(cfg, p, b, policy,
                                              cache_len=cache_len))
    decode = jax.jit(lambda p, t, s: lm.decode_step(cfg, p, t, s, policy))

    monitor = (_build_monitor(args, cfg, transit_bridge)
               if args.monitor_every else None)
    engine = (_attach_monitor_engine(args, monitor, transit_bridge)
              if monitor is not None else None)
    snapshots = 0

    with jax.set_mesh(mesh):
        t0 = time.perf_counter()
        logits, state = prefill(params, {"tokens": prompts})
        logits.block_until_ready()
        t_prefill = time.perf_counter() - t0

        out_tokens = []
        tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
        t0 = time.perf_counter()
        for step in range(args.tokens):
            out_tokens.append(np.asarray(tok))
            logits, state = decode(params, tok, state)
            tok = jnp.argmax(logits[:, -1], axis=-1)[:, None] \
                     .astype(jnp.int32)
            if engine is not None and step % args.monitor_every == 0:
                # submit the (still in-flight) logits to the monitor
                # bucket; the engine coalesces --monitor-batch of them
                # into ONE batched chain execute per tick — the decode
                # loop never waits for the analysis
                engine.submit(logits[:, -1], bucket="monitor")
                snapshots += 1
                engine.step()
                if elastic is not None:
                    # lease renewal + failure poll at monitor cadence;
                    # tick() is collective and every process reaches
                    # this point at the same decode step
                    elastic.heartbeat_all()
                    elastic.tick()
        jax.block_until_ready(logits)
        t_decode = time.perf_counter() - t0
        if engine is not None:
            # trailing partial batch: a different leading dim means a
            # fresh trace — same flush helper as the in-loop ticks,
            # forced, outside the timed decode window
            engine.flush()
            engine.drain()

    gen = np.concatenate(out_tokens, axis=1)
    report = {
        "arch": cfg.name,
        "batch": args.batch,
        "prefill_ms": round(t_prefill * 1e3, 2),
        "decode_ms_per_token": round(t_decode / args.tokens * 1e3, 3),
        "tokens_per_s": round(args.batch * args.tokens / t_decode, 1),
        "sample": gen[0, :8].tolist(),
    }
    if monitor is not None:
        monitor.drain()
        erep = engine.report()
        engine.stop()
        mrep = monitor.marshaling_report()
        files = monitor.finalize()["writer"]["files"]
        pipe = mrep.get("pipeline", {})
        report["monitor"] = {
            "submits": erep["batching"]["executes"],
            "snapshots": snapshots,
            "snapshot_batch": args.monitor_batch,
            "files": len(files),
            "overlap_efficiency": round(
                pipe.get("overlap_efficiency", 0.0), 3),
            "host_busy_ms": round(pipe.get("host_busy_s", 0.0) * 1e3, 2),
            "backpressure_ms": round(
                pipe.get("backpressure_s", 0.0) * 1e3, 2),
            "engine": {
                "batched_execute_ratio":
                    erep["batching"]["batched_execute_ratio"],
                "submit_us_p50": erep["latency_ms"]["p50"] * 1e3,
                "submit_us_p99": erep["latency_ms"]["p99"] * 1e3,
                "queue_depth_max": erep["queue"]["depth_max"],
            },
        }
    if transit_bridge is not None:
        # controller.report() nests the live bridge's transit accounting
        report["elastic" if elastic is not None else "transit"] = \
            transit_bridge.report()
    if args.bench_out and jax.process_index() == 0:
        _emit_report_rows(report, args.bench_out)
        print(f"serve: decode {report['decode_ms_per_token']} ms/token, "
              f"{report['tokens_per_s']} tok/s -> {args.bench_out}")
    else:
        print(json.dumps(report))
    return report


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
