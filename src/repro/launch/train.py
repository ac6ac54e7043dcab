"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch phi3-mini-3.8b \
        --steps 300 --seq 128 --batch 8 --reduced --ckpt /tmp/ckpt \
        --restore auto

Production posture on one host: the same loop a multi-pod launch runs —
jitted train step with sharded state, step-atomic async checkpoints,
resume-from-latest-valid, preemption flush (SIGTERM), and a data pipeline
addressed purely by (seed, step) so restarts and elastic re-shards never
replay or skip data.  `--mesh` activates a (data, model) mesh over
however many devices exist (tests use CPU device_count=1).
"""
from __future__ import annotations

import argparse
import sys
import threading
import time

import jax

from repro import obs
from repro.obs.flight import flight

# the most recent ObsServer started by main() — see launch/serve.py
last_server: obs.ObsServer = None
from repro.checkpoint.manager import CheckpointManager
from repro.configs import TrainConfig, get_config, reduced_config
from repro.configs.base import ShapeConfig
from repro.data.pipeline import Loader, SyntheticLM
from repro.distributed import sharding as shd
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_test_mesh
from repro.training import loop as tl


def main(argv=None) -> int:
    global last_server
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--reduced", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--restore", choices=("auto", "none"), default="auto")
    ap.add_argument("--mesh", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--compression", choices=("none", "int8"),
                    default="none")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a Chrome/Perfetto trace of the run here")
    ap.add_argument("--chaos-seed", type=int, default=None, metavar="SEED",
                    help="inject a seeded fault plan (transient step "
                         "crashes, corrupt checkpoint shards) and run "
                         "through the recovery loop")
    ap.add_argument("--max-restarts", type=int, default=3,
                    help="transient-fault restarts before giving up")
    ap.add_argument("--grad-skip-threshold", type=float, default=0.0,
                    help="skip optimizer updates whose global grad norm "
                         "is non-finite or above this (0 = off)")
    ap.add_argument("--metrics-port", type=int, default=None, metavar="PORT",
                    help="serve /metrics + /healthz on this port; 0 picks "
                         "an ephemeral port; default off")
    ap.add_argument("--flight-dir", default=None, metavar="DIR",
                    help="enable the flight recorder; dumps flight_*.json "
                         "here on crash or SIGUSR1")
    args = ap.parse_args(argv)
    if args.trace:
        obs.enable_tracing()

    cfg = reduced_config(args.arch) if args.reduced else get_config(args.arch)
    tc = TrainConfig(learning_rate=args.lr, total_steps=args.steps,
                     warmup_steps=max(args.steps // 10, 1),
                     microbatch=args.microbatch or None,
                     grad_compression=args.compression,
                     grad_skip_threshold=args.grad_skip_threshold)
    shape = ShapeConfig("cli", seq_len=args.seq, global_batch=args.batch,
                        kind="train")

    mesh = make_test_mesh(data=len(jax.devices()), model=1) \
        if args.mesh else None
    rules = shd.train_rules(mesh) if mesh else None

    state = tl.init_train_state(jax.random.PRNGKey(tc.seed), cfg, tc)
    step_fn = jax.jit(tl.make_train_step(cfg, tc), donate_argnums=(0,))

    source = SyntheticLM(cfg, shape, seed=tc.seed)
    loader = Loader(source)

    injector = None
    if args.chaos_seed is not None:
        from repro import faults
        injector = faults.FaultInjector(
            faults.training_plan(args.chaos_seed, horizon=args.steps))

    # live observability plane (default off; see launch/serve.py for the
    # serving twin of this wiring)
    live = obs.Liveness(max_age_s=30.0)     # train steps can be slow on CPU
    if args.flight_dir:
        flight.enable()
        flight.attach_tracer(obs.tracer)
        flight.add_metrics_source(obs.metrics)
        if injector is not None:
            flight.add_metrics_source(injector.metrics)
        if threading.current_thread() is threading.main_thread():
            flight.install_signal_handler(
                args.flight_dir,
                callback=lambda p: print(f"[flight] wrote {p}", flush=True))
    server = None
    if args.metrics_port is not None:
        server = obs.ObsServer(
            port=args.metrics_port,
            registries=[obs.metrics]
            + ([injector.metrics] if injector is not None else []),
            health=live, flight=flight)
        port = server.start()
        last_server = server
        print(f"[obs] live plane on http://127.0.0.1:{port}"
              f"  (/metrics /healthz /debug/flight)", flush=True)

    start = 0
    mgr = None
    if args.ckpt:
        mgr = CheckpointManager(args.ckpt, keep=3, injector=injector)
        if args.restore == "auto":
            got = mgr.restore_latest(state)
            if got is not None:
                start, state, meta = got
                loader.load_state_dict({"step": meta.get("data_step", start),
                                        "seed": tc.seed})
                print(f"[restore] resumed from step {start}", flush=True)
        mgr.install_preemption_flush(lambda: (loader.step, state))

    if injector is not None:
        # chaos mode: run through the recovery loop (sync checkpoints,
        # auto-resume from the newest verified checkpoint on crash)
        from repro.training.resilient import train_with_recovery

        def on_step(step, st, metrics):
            live.beat()
            if step % args.log_every == 0:
                m = {k: float(v) for k, v in metrics.items()}
                print(f"step {step:5d}  loss {m['loss']:.4f}  "
                      f"gnorm {m['grad_norm']:.2f}", flush=True)

        try:
            with shd.axis_rules(mesh, rules):
                state, restarts = train_with_recovery(
                    state, step_fn, loader,
                    total_steps=args.steps, start_step=start,
                    manager=mgr, checkpoint_every=args.ckpt_every,
                    injector=injector, max_restarts=args.max_restarts,
                    registry=obs.metrics, on_step=on_step)
        except BaseException as e:
            if args.flight_dir:
                path = flight.crash_dump(args.flight_dir, e)
                print(f"[flight] crash dump: {path}", flush=True)
            if server is not None:
                server.stop()
            raise
        live.done()
        print(f"[chaos] restarts={restarts} "
              f"faults_remaining={injector.remaining()}", flush=True)
        for key, s in sorted(injector.metrics.snapshot().items()):
            print(f"  {key}: {s.get('value')}", flush=True)
        if args.flight_dir:
            reason = ("fault-plan-exhausted" if injector.remaining() == 0
                      else "chaos-run-end")
            path = flight.dump(args.flight_dir, reason=reason)
            print(f"[flight] wrote {path}", flush=True)
        if server is not None:
            server.stop()
        print("[done]", flush=True)
        return 0

    try:
        _train_plain(args, mesh, rules, state, step_fn, loader, mgr, live,
                     shape, start)
    except BaseException as e:
        if args.flight_dir:
            path = flight.crash_dump(args.flight_dir, e)
            print(f"[flight] crash dump: {path}", flush=True)
        if server is not None:
            server.stop()
        raise
    live.done()
    if args.trace:
        obs.write_chrome_trace(args.trace, obs.tracer.drain())
        print(f"[trace] wrote {args.trace}", flush=True)
    if server is not None:
        server.stop()
    print("[done]", flush=True)
    return 0


def _train_plain(args, mesh, rules, state, step_fn, loader, mgr, live,
                 shape, start):
    """The fault-free training loop (chaos runs go through
    training.resilient instead)."""
    ctx = shd.axis_rules(mesh, rules)
    with ctx:
        t0 = time.time()
        t_prev = time.perf_counter()
        for step in range(start, args.steps):
            live.beat()
            batch = next(loader)
            with obs.trace.span("train_step", step=step + 1):
                state, metrics = step_fn(state, batch)
            if (step + 1) % args.log_every == 0 or step == start:
                m = {k: float(v) for k, v in metrics.items()}
                now = time.perf_counter()
                tl.record_step_metrics(
                    obs.metrics, m, step=step + 1,
                    tokens=shape.tokens, dt=now - t_prev)
                t_prev = now
                tok_s = shape.tokens * (step + 1 - start) / (time.time() - t0)
                print(f"step {step+1:5d}  loss {m['loss']:.4f}  "
                      f"ce {m['ce']:.4f}  gnorm {m['grad_norm']:.2f}  "
                      f"lr {m['lr']:.2e}  tok/s {tok_s:,.0f}", flush=True)
            else:
                t_prev = time.perf_counter()
            if mgr and (step + 1) % args.ckpt_every == 0:
                with obs.trace.span("checkpoint", step=step + 1):
                    mgr.async_save(step + 1, state,
                                   {"data_step": loader.step})
        if mgr:
            mgr.wait()
            mgr.save(args.steps, state, {"data_step": loader.step})


if __name__ == "__main__":
    sys.exit(main())
