"""Training driver: ``python -m repro.launch.train --arch qwen3-4b --reduced …``

Wires every substrate layer together: config registry → model → sharded
train step (policy from the live mesh) → deterministic data pipeline →
AdamW → checkpoint/restart loop with straggler monitoring → optional
in-situ spectral-monitor chain running inside the step (the paper's
technique attached to training as a first-class feature).

On this CPU container use ``--reduced`` (small same-family config); on a
real TPU fleet the same entry point runs the full configs over
``make_production_mesh()``.
"""
from __future__ import annotations

import argparse
import json
import time
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp


from repro.configs import registry
from repro.core.fft import plan as plan_mod
from repro.core.insitu.chain import InSituChain
from repro.core.insitu.endpoints.spectral_monitor import SpectralMonitorEndpoint
from repro.data import synthetic
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models import lm
from repro.optim.adamw import AdamW, warmup_cosine
from repro.runtime.cluster import (add_cluster_args, config_from_args,
                                   init_cluster)
from repro.runtime.fault import run_with_restarts
from repro.sharding.policy import make_policy
from repro.train import step as train_step_mod


def _discard(_data):
    """--transit-async on_result for producer-only processes: their
    send() result is a None-leaved placeholder — drop it instead of
    letting the async hop retain it until drain."""


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="results/ckpt")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--insitu-every", type=int, default=10)
    ap.add_argument("--no-insitu", action="store_true")
    ap.add_argument("--insitu-spectra-dir", default=None,
                    help="persist per-report gradient spectra through a "
                         "pipelined host-offload chain (the .npy writes "
                         "overlap the next train step)")
    ap.add_argument("--transit-consumers", type=int, default=0,
                    metavar="N",
                    help="in-transit M→N split: train on all but the "
                         "last N devices and deliver the in-situ "
                         "spectra to a disjoint N-device consumer mesh "
                         "through core/insitu/transit.TransitBridge "
                         "(0 = analyze in place). Multi-process "
                         "clusters: every process must keep at least "
                         "one producer device or the run aborts "
                         "(docs/multihost.md, subset collectives)")
    ap.add_argument("--transit-async", action="store_true",
                    help="overlap the M→N transit hop with the next "
                         "train step: send_async() snapshots the "
                         "report and a bounded background worker runs "
                         "the exchange plus the consumer-side chain; "
                         "a failed hop surfaces on the next send or "
                         "drain (requires --transit-consumers; "
                         "docs/multihost.md)")
    ap.add_argument("--elastic", action="store_true",
                    help="put the transit consumer mesh under an "
                         "ElasticController: consumer ranks heartbeat "
                         "every in-situ report, a rank that misses its "
                         "lease is rescaled away (and can rejoin) "
                         "without restarting the producer "
                         "(docs/elastic.md; requires "
                         "--transit-consumers)")
    ap.add_argument("--elastic-lease", type=float, default=30.0,
                    metavar="SECONDS",
                    help="heartbeat lease; a consumer rank missing 3 "
                         "leases is declared dead")
    ap.add_argument("--fail-at", type=int, nargs="*", default=None,
                    help="inject failures at these steps (FT test)")
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--wisdom", default=None, metavar="FILE",
                    help="persistent autotune wisdom file: measured "
                         "sweep winners are read at bring-up and new "
                         "ones persisted, so restarts skip the timed "
                         "sweeps (overrides REPRO_WISDOM_FILE; "
                         "docs/wisdom.md)")
    ap.add_argument("--wisdom-mode", default="readwrite",
                    choices=("off", "read", "readwrite"),
                    help="read = consult wisdom but never write it")
    add_cluster_args(ap)
    args = ap.parse_args(argv)
    if args.wisdom:
        # before any measured planning (restarts warm-start from it)
        plan_mod.set_wisdom(args.wisdom, args.wisdom_mode)
    # multi-process bring-up (env/flag-driven; single-process no-op) —
    # must precede the first device query below
    init_cluster(config_from_args(args))
    if jax.process_count() > 1:
        # every process snapshots (replicated state, same bytes), so
        # sharing one directory is a tmp-dir rename race — give each
        # process its own
        args.ckpt_dir = str(Path(args.ckpt_dir)
                            / f"proc{jax.process_index()}")

    cfg = (registry.get_reduced(args.arch) if args.reduced
           else registry.get_config(args.arch))
    transit_bridge = None
    elastic = None
    if args.transit_consumers:
        # M→N in-transit: the model trains on a producer mesh that
        # excludes the last N devices; spectra hop to the consumer mesh
        if args.elastic:
            # consumer side under an ElasticController: the controller
            # duck-types the bridge, so every send below routes to the
            # newest generation's mesh
            from repro.launch.mesh import make_elastic_setup
            mesh, elastic = make_elastic_setup(
                args.transit_consumers, lease=args.elastic_lease)
            transit_bridge = elastic
        else:
            from repro.launch.mesh import make_transit_setup
            mesh, transit_bridge = make_transit_setup(
                args.transit_consumers)
    elif args.elastic:
        raise SystemExit("--elastic requires --transit-consumers N "
                         "(there is no consumer mesh to rescale)")
    else:
        mesh = (make_production_mesh() if args.production_mesh
                else make_host_mesh())
    if args.transit_async and not args.transit_consumers:
        raise SystemExit("--transit-async requires --transit-consumers N "
                         "(there is no transit hop to overlap)")
    policy = make_policy(mesh, global_batch=args.batch)

    opt = AdamW(warmup_cosine(args.lr, max(args.steps // 20, 1),
                              args.steps))

    insitu_chain = None
    if not args.no_insitu:
        insitu_chain = InSituChain(
            [SpectralMonitorEndpoint(source="grads", nbins=8,
                                     max_tensors=4)],
            mesh=mesh).initialize()

    spectra_chain = None
    if args.insitu_spectra_dir and not args.no_insitu:
        # host offload of the monitor's spectra: the writer runs on the
        # pipeline worker, so disk I/O overlaps the next train step
        from repro.core.insitu.endpoints.writer import WriterEndpoint
        spectra_chain = InSituChain(
            [WriterEndpoint(array="insitu_grad_spectra",
                            out_dir=args.insitu_spectra_dir,
                            prefix="spectra")],
            mode="pipelined", pipeline_depth=2).initialize()

    step_fn = train_step_mod.make_train_step(
        cfg, policy, opt, microbatches=args.microbatches,
        loss_chunk=min(args.seq, 512),
        insitu_chain=(insitu_chain.as_step_hook() if insitu_chain
                      else None),
        insitu_every=args.insitu_every)
    step_fn = jax.jit(step_fn, donate_argnums=(0,))

    def make_state():
        return train_step_mod.init_train_state(
            cfg, opt, jax.random.PRNGKey(args.seed),
            param_dtype=jnp.float32, max_target=args.seq)

    def batch_fn(step):
        b = synthetic.batch_at(
            step, global_batch=args.batch, seq_len=args.seq,
            vocab=cfg.vocab_size, seed=args.seed, family=cfg.family,
            num_patches=min(cfg.num_patches, args.seq // 2),
            patch_dim=lm.VIT_STUB_DIM, frame_dim=cfg.d_model)
        return {k: jnp.asarray(v) for k, v in b.items()}

    losses = []

    spectra_last = [-1]

    def on_metrics(step, metrics):
        loss = float(metrics["loss"])
        losses.append(loss)
        # on_metrics receives the post-increment step: metrics describe
        # train-step `step - 1`, the one the in-step monitor's lax.cond
        # keyed on
        monitor_step = step - 1
        if spectra_chain is not None and "insitu" in metrics \
                and monitor_step % args.insitu_every == 0 \
                and monitor_step > spectra_last[0]:
            # cadence guard: the monitor publishes zeros on the steps it
            # skips (lax.cond's other branch) — only real report steps
            # go to disk. monotonic guard: restart-on-failure replays
            # steps already reported, and the writer's file list must
            # stay one entry per step, in step order.
            spectra_last[0] = monitor_step
            from repro.core.insitu.bridge import BridgeData
            payload = BridgeData(arrays=dict(metrics["insitu"]),
                                 step=monitor_step)
            deliver = True
            if transit_bridge is not None:
                # hop onto the consumer mesh: the writer chain's work
                # (and any future consumer-side analysis) leaves the
                # training devices entirely. send() is collective —
                # every process calls it — but only consumer
                # participants receive the arrays (host transport
                # hands producers None leaves), so only they run the
                # chain; producer-only processes still fall through to
                # the progress log below
                if args.transit_async:
                    # async hop: the bounded worker runs the exchange
                    # and (on consumers) the writer chain, overlapping
                    # the next train step; a failed hop raises a
                    # contained PipelineError at the next send/drain
                    transit_bridge.send_async(
                        payload,
                        on_result=(spectra_chain.execute
                                   if transit_bridge.is_consumer()
                                   else _discard))
                    deliver = False
                else:
                    payload = transit_bridge.send(payload)
                    deliver = transit_bridge.is_consumer()
            if deliver:
                spectra_chain.execute(payload)
        if elastic is not None and monitor_step % args.insitu_every == 0:
            # lease renewal + failure poll at monitor cadence; tick()
            # is collective, and every process reaches this point at
            # the same step, matching its contract
            if args.transit_async:
                # tick() runs host collectives; an in-flight async
                # send must never interleave with them (the send_async
                # contract in core/insitu/transit.py) — drain first
                transit_bridge.drain_async()
            elastic.heartbeat_all()
            elastic.tick()
        if step % 10 == 0 or step <= 2:
            extra = ""
            if "insitu" in metrics:
                hf = metrics["insitu"].get("insitu_highfreq_frac")
                if hf is not None:
                    extra = f" gradHF={float(hf):.3f}"
            print(f"step {step:5d} loss {loss:.4f} "
                  f"lr {float(metrics['lr']):.2e}"
                  f" gnorm {float(metrics['grad_norm']):.2f}{extra}",
                  flush=True)

    t0 = time.time()
    with jax.set_mesh(mesh):
        state, report = run_with_restarts(
            make_state=make_state, train_step=step_fn, batch_fn=batch_fn,
            total_steps=args.steps, ckpt_dir=args.ckpt_dir,
            ckpt_every=args.ckpt_every, fail_at=args.fail_at,
            on_metrics=on_metrics)

    out = {"arch": cfg.name, "steps": args.steps,
           "first_loss": losses[0] if losses else None,
           "final_loss": losses[-1] if losses else None,
           "wall_s": round(time.time() - t0, 1), **report}
    if transit_bridge is not None and args.transit_async:
        # consumer-side chain work runs on the async worker — complete
        # (and surface any contained failure from) every pending hop
        # before the chain drains and the bridge reports
        transit_bridge.drain_async()
    if spectra_chain is not None:
        spectra_chain.drain()
        pipe = spectra_chain.marshaling_report().get("pipeline", {})
        out["spectra_files"] = len(
            spectra_chain.finalize()["writer"]["files"])
        out["spectra_backpressure_ms"] = round(
            pipe.get("backpressure_s", 0.0) * 1e3, 2)
    if transit_bridge is not None:
        # controller.report() nests the live bridge's transit accounting
        out["elastic" if elastic is not None else "transit"] = \
            transit_bridge.report()
    print(json.dumps(out, default=str))
    return out


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
