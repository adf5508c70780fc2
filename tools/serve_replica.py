#!/usr/bin/env python
"""Run one fleet replica as a process: ``serve.Engine`` behind the
``fleet.ReplicaServer`` HTTP front (/generate, /healthz, /drain,
/statusz.json).

This is the process target ``fleet.Supervisor`` spawns and
``tools/fleet_bench.py`` load-tests.  It builds a checkpoint-shaped
random GPT deterministically from ``--seed`` — every replica started
with the same model flags and seed holds IDENTICAL weights, which is
what makes router retry-on-sibling token-identical (greedy decode +
same weights = same tokens on any replica).

Startup is warm when the AOT env is set (docs/how_to/startup.md):
``MXTPU_AOT_DIR`` loads exported bucket programs instead of tracing,
``MXTPU_WARMUP_MANIFEST`` replays the traffic manifest before the
ready line prints — the drain -> restart path a rolling restart rides.

Faults: ``MXTPU_FAULT_SPEC`` (docs/how_to/fleet.md) arms the
deterministic chaos injector; a *kill* fault here is a real
``os._exit(1)`` mid-request.

Prints exactly one ready line to stdout once serving::

  {"ready": true, "port": N, "host": ..., "pid": ..., "replica_id":
   ..., "backend": "cpu", "ready_s": 1.23, "warmed": 10}

then serves until SIGTERM/SIGINT (clean engine shutdown), the process
is killed, or — with ``--exit-on-drained`` — a requested drain
completes (exit 0; the supervisor treats it as drain-done).
"""

import argparse
import json
import os
import signal
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def build_model(mx, args):
    """Deterministic tiny/medium GPT + params from the CLI config —
    byte-identical across replicas sharing flags and seed."""
    import numpy as np

    max_len = args.max_model_len
    kv = args.kv_heads or max(1, args.heads // 4)
    net = mx.models.gpt(args.vocab, max_len, num_layers=args.layers,
                        d_model=args.d_model, num_heads=args.heads,
                        norm="rmsnorm", mlp="swiglu", pos_embed="rope",
                        tie_embeddings=True, kv_heads=kv)
    arg_shapes, _, _ = net.infer_shape(data=(1, max_len),
                                       softmax_label=(1, max_len))
    rng = np.random.RandomState(args.seed)
    params = {}
    for name, shp in zip(net.list_arguments(), arg_shapes):
        if name in ("data", "softmax_label"):
            continue
        # 0.35 weight scale gives greedy argmax varied (non-degenerate)
        # token sequences — the same recipe the serve tests use
        scale = 0.35 if name.endswith("weight") else 0.0
        params[name] = (rng.randn(*shp) * scale
                        + (1.0 if name.endswith("gamma") else 0.0)
                        ).astype(np.float32)
    return net, params


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=0,
                   help="0 = ephemeral (read the ready line)")
    p.add_argument("--replica-id", default=None)
    # model config (defaults: CPU-tractable smoke shared with
    # fleet_bench; all replicas in one fleet MUST share these + --seed)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--d-model", type=int, default=64)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--kv-heads", type=int, default=None)
    p.add_argument("--vocab", type=int, default=512)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--version", default=None,
                   help="deploy identity tag surfaced on /healthz, "
                        "/statusz.json and the ready line (default: "
                        "a short digest of the model config + seed — "
                        "the synthetic-checkpoint equivalent of a "
                        "checkpoint digest)")
    # engine config
    p.add_argument("--block-size", type=int, default=8)
    p.add_argument("--num-blocks", type=int, default=128)
    p.add_argument("--max-batch", type=int, default=4)
    p.add_argument("--max-queue", type=int, default=32)
    p.add_argument("--max-model-len", type=int, default=64)
    p.add_argument("--max-prefills", type=int, default=2)
    p.add_argument("--tenant-share", type=float, default=None,
                   help="fair-share fraction of the queue per tenant "
                        "(default MXTPU_SERVE_TENANT_SHARE / 1.0 = off)")
    p.add_argument("--role", choices=("both", "prefill", "decode"),
                   default=None,
                   help="disaggregation role (default MXTPU_FLEET_ROLE "
                        "/ both): prefill replicas answer /generate "
                        "with a KV handoff envelope, decode replicas "
                        "serve /handoff ingests only")
    p.add_argument("--host-kv-bytes", type=int, default=None,
                   help="host-RAM KV tier byte budget (default "
                        "MXTPU_SERVE_HOST_KV_BYTES; a decode role "
                        "without one gets a 256 MiB default — handoff "
                        "records land in this pool)")
    p.add_argument("--warmup", choices=("auto", "full", "none"),
                   default="auto",
                   help="auto: replay MXTPU_WARMUP_MANIFEST when set; "
                        "full: pre-compile the whole bucket grid; "
                        "none: compile lazily on traffic")
    p.add_argument("--model", default=None,
                   help="catalog model id advertised on /healthz and "
                        "/statusz.json (default MXTPU_FLEET_MODEL / "
                        "unset): the router only sends requests "
                        "naming a model to replicas carrying it")
    p.add_argument("--adapters", type=int, default=None,
                   help="LoRA adapter device slots incl. the reserved "
                        "base slot 0 (default MXTPU_SERVE_ADAPTERS / "
                        "0 = multiplexing off)")
    p.add_argument("--adapter-rank", type=int, default=None,
                   help="padded LoRA rank ceiling for the adapter "
                        "stacks (default MXTPU_SERVE_ADAPTER_RANK / 8)")
    p.add_argument("--exit-on-drained", action="store_true",
                   help="exit 0 once a requested drain completes "
                        "(the supervisor's rolling-restart handshake)")
    p.add_argument("--backend", "--platform", dest="platform",
                   default=None)
    args = p.parse_args()

    if args.platform:
        os.environ["JAX_PLATFORMS"] = args.platform

    t0 = time.perf_counter()
    import mxnet_tpu as mx

    import jax

    net, params = build_model(mx, args)
    role = args.role or os.environ.get("MXTPU_FLEET_ROLE") or "both"
    host_kv = args.host_kv_bytes
    if host_kv is None and role == "decode" \
            and not os.environ.get("MXTPU_SERVE_HOST_KV_BYTES"):
        # a decode replica's entire purpose is ingesting handoff KV —
        # it needs the host tier; default a 256 MiB pool when nothing
        # was configured (tiny smoke models use a fraction of it)
        host_kv = 256 << 20
    engine = mx.serve.Engine(
        params, symbol=net, block_size=args.block_size,
        num_blocks=args.num_blocks, max_batch=args.max_batch,
        max_queue=args.max_queue, max_model_len=args.max_model_len,
        max_prefills_per_step=args.max_prefills,
        tenant_share=args.tenant_share, host_kv_bytes=host_kv,
        adapters=args.adapters, adapter_rank=args.adapter_rank)
    warmed = 0
    if args.warmup == "full":
        warmed = engine.warmup()
    elif args.warmup == "auto" and os.environ.get("MXTPU_WARMUP_MANIFEST"):
        warmed = engine.warmup()

    version = args.version
    if version is None:
        # weights here are a pure function of the model flags + seed,
        # so their digest is: same version tag <=> identical weights
        import hashlib
        cfg = (f"{args.layers}/{args.d_model}/{args.heads}/"
               f"{args.kv_heads}/{args.vocab}/{args.max_model_len}/"
               f"{args.seed}")
        version = "cfg-" + hashlib.sha1(cfg.encode()).hexdigest()[:10]

    replica = mx.fleet.ReplicaServer(
        engine, host=args.host, port=args.port,
        replica_id=args.replica_id, role=role, version=version,
        model=args.model,
        on_kill=lambda: os._exit(1))       # a kill fault is a real death
    replica.start()

    def _term(signum, frame):
        replica.stop()
        sys.exit(0)

    signal.signal(signal.SIGTERM, _term)
    signal.signal(signal.SIGINT, _term)

    print(json.dumps({
        "ready": True, "port": replica.port, "host": args.host,
        "pid": os.getpid(), "replica_id": replica.replica_id,
        "role": replica.role,
        "version": replica.version,
        "model": replica.model,
        "backend": jax.default_backend(),
        "ready_s": round(time.perf_counter() - t0, 3),
        "warmed": warmed,
        "aot_dir": os.environ.get("MXTPU_AOT_DIR"),
        "fault_spec": os.environ.get("MXTPU_FAULT_SPEC") or None}),
        flush=True)

    while replica.state != mx.fleet.DEAD:
        if args.exit_on_drained and replica.drained():
            # give the drain's last /healthz polls a beat to observe
            # the completed state, then leave cleanly
            time.sleep(0.2)
            replica.stop()
            return 0
        time.sleep(0.1)
    return 1        # hard-stopped (engine step failure) — supervisor restarts


if __name__ == "__main__":
    sys.exit(main())
