"""CLI: `python -m bee2bee_tpu <command>` (reference __main__.py:30-123's
click group, with `serve-tpu` as the flagship alongside the reference's
backends and `register` for one-shot registry upserts)."""

from __future__ import annotations

import asyncio
import logging
import os

import click

from . import __version__
from .config import load_config, save_config


def _setup_logging():
    fmt = "%(asctime)s %(name)s %(levelname)s %(message)s"
    logging.basicConfig(level=os.environ.get("LOG_LEVEL", "INFO"), format=fmt)
    # rotating file sink alongside stderr (the reference's loguru setup:
    # reference __main__.py:13-16). BEE2BEE_LOG_FILE overrides the path;
    # set it empty to disable. The default is per-PROCESS (pid suffix):
    # two processes rotating one shared file clobber each other's backups
    # — an explicit BEE2BEE_LOG_FILE opts into sharing deliberately.
    log_file = os.environ.get("BEE2BEE_LOG_FILE")
    if log_file is None:
        import contextlib
        import time as _time

        from .utils import bee2bee_home

        home = bee2bee_home()
        # reap per-pid logs of DEAD processes (>7 days) — a quiet but
        # live daemon's open log must never be unlinked out from under
        # its handler
        cutoff = _time.time() - 7 * 86400
        for old in home.glob("bee2bee-*.log*"):
            with contextlib.suppress(OSError, ValueError):
                pid = int(old.name.split("-", 1)[1].split(".", 1)[0])
                if pid == os.getpid():
                    continue
                try:
                    os.kill(pid, 0)  # raises if the pid is gone
                    continue  # still alive: keep its logs
                except ProcessLookupError:
                    pass
                except PermissionError:
                    continue  # alive under another uid
                if old.stat().st_mtime < cutoff:
                    old.unlink()
        log_file = str(home / f"bee2bee-{os.getpid()}.log")
    if log_file:
        from logging.handlers import RotatingFileHandler

        try:
            handler = RotatingFileHandler(
                log_file, maxBytes=5 * 1024 * 1024, backupCount=3
            )
            handler.setFormatter(logging.Formatter(fmt))
            logging.getLogger().addHandler(handler)
        except OSError:  # read-only fs etc. — stderr logging still works
            pass
    # orbax/absl emit per-save INFO floods; keep them at WARNING unless asked
    if os.environ.get("LOG_LEVEL", "INFO").upper() != "DEBUG":
        logging.getLogger("absl").setLevel(logging.WARNING)


def _apply_common_cfg(cfg, kw):
    """Fold _common_opts (and mesh shape) into the node config."""
    if kw.get("port") is not None:
        cfg.port = kw["port"]
    if kw.get("api_port") is not None:
        cfg.api_port = kw["api_port"]
    if kw.get("price") is not None:
        cfg.price_per_token = kw["price"]
    if kw.get("mesh_shape"):
        cfg.mesh_shape = kw["mesh_shape"]
    if kw.get("attention"):
        cfg.attention = kw["attention"]
    if kw.get("quantize"):
        cfg.quantize = kw["quantize"]
    if kw.get("kv_quant"):
        cfg.kv_quant = True
    if kw.get("spec_tokens") is not None:
        cfg.spec_tokens = kw["spec_tokens"]
    if kw.get("drafter") is not None:
        cfg.drafter = kw["drafter"]
    if kw.get("adapters"):
        cfg.adapters = kw["adapters"]
    if kw.get("max_adapters") is not None:
        cfg.max_adapters = kw["max_adapters"]
    return cfg


def _serve(backend: str, model: str, **kw):
    from .meshnet.runtime import run_p2p_node

    _setup_logging()
    if backend == "tpu":
        from .utils import enable_compile_cache

        logging.getLogger("bee2bee_tpu").info(
            "jax compile cache: %s", enable_compile_cache()
        )
    cfg = _apply_common_cfg(load_config(), kw)
    try:
        asyncio.run(
            run_p2p_node(
                backend=backend,
                model=model,
                cfg=cfg,
                bootstrap=kw.get("bootstrap"),
                checkpoint_path=kw.get("checkpoint"),
                lora_path=kw.get("lora"),
                ollama_host=kw.get("ollama_host"),
                publish_weights=kw.get("publish_weights", False),
                from_mesh=kw.get("from_mesh", False),
                tunnel=kw.get("tunnel"),
            )
        )
    except KeyboardInterrupt:
        click.echo("shutting down")


def _microbatches_arg(ctx, param, value):
    """'auto' or an int >= 1 — validated at CLI parse, not minutes later
    inside the async serve body after the stages compiled."""
    if value == "auto":
        return value
    try:
        iv = int(value)
    except (TypeError, ValueError):
        raise click.BadParameter("must be 'auto' or a positive integer")
    if iv < 1:
        raise click.BadParameter("must be >= 1")
    return iv


def _common_opts(f):
    f = click.option("--port", type=int, default=None, help="WS mesh port")(f)
    f = click.option("--api-port", type=int, default=None, help="HTTP gateway port")(f)
    f = click.option("--bootstrap", default=None, help="bootstrap ws:// addr or join link")(f)
    f = click.option("--price", type=float, default=None, help="price per token")(f)
    f = click.option(
        "--tunnel",
        type=click.Choice(["auto", "bore", "ngrok", "cloudflared", "stub"]),
        default=None,
        help="expose this node through a public tunnel and announce its "
             "address (cloud/Colab onboarding — docs/CLOUD_NODE.md)",
    )(f)
    return f


@click.group()
@click.version_option(__version__)
def cli():
    """bee2bee-tpu: TPU-native decentralized inference mesh."""


@cli.command("serve-tpu")
@click.option("--model", default="distilgpt2",
              help="model name or config key; 'auto' derives the "
                   "architecture from --checkpoint's config.json (serves "
                   "checkpoints with no registry entry)")
@click.option("--checkpoint", default=None, help="local checkpoint dir (HF or native)")
@click.option("--lora", default=None, type=click.Path(exists=True),
              help="LoRA adapters .npz to merge over the base (bee2bee-tpu "
                   "train --lora-rank)")
@click.option("--mesh-shape", default=None, help='e.g. "data:1,model:8" or "seq:4,model:2"')
@click.option("--attention", type=click.Choice(["auto", "dense", "flash", "sp"]), default=None,
              help="auto (flash on TPU when supported) | dense | flash "
                   "(ragged paged pallas kernel; composes with --spec) | sp "
                   "(pool slot dim sharded over seq for long context)")
@click.option("--quantize", type=click.Choice(["none", "int8"]), default=None,
              help="weight-only quantization (int8 halves decode HBM traffic)")
@click.option("--kv-quant", "kv_quant", is_flag=True, default=False,
              help="int8 KV pool: pages stored int8 with per-page-per-head "
                   "scales, dequantized inside the attention kernels — ~2x "
                   "resident sessions at fixed HBM and half the migration "
                   "bytes (BEE2BEE_KV_QUANT; bf16 pool default)")
@click.option("--spec", "spec_tokens", type=int, default=None,
              help="self-speculative decoding: draft up to N tokens per "
                   "step by n-gram lookup over the request's own "
                   "prompt+output and verify them in one batched forward "
                   "(greedy rows; BEE2BEE_SPEC; 0 = off)")
@click.option("--drafter", default=None,
              help="model-tier speculative drafter (requires --spec > 0): a "
                   "registry model name or checkpoint dir loaded resident "
                   "beside the target, or 'mesh' to stream drafts from a "
                   "BEE2BEE_DISAGG=draft peer. Rows where the n-gram tier "
                   "disables itself escalate to this tier instead of going "
                   "dark (BEE2BEE_DRAFTER; empty = n-gram only)")
@click.option("--adapters", default=None,
              help="batched multi-LoRA serving: comma-separated "
                   "name=path.npz adapters preloaded into the hot-swap "
                   "pool and published on the DHT — clients select one "
                   "via model='<base>:<name>' on /v1 (BEE2BEE_ADAPTERS; "
                   "composes with on-demand mesh paging)")
@click.option("--max-adapters", "max_adapters", type=int, default=None,
              help="adapter pool slots (BEE2BEE_MAX_ADAPTERS; --adapters "
                   "implies 8). Non-resident adapters page in from mesh "
                   "peers, LRU-evicting cold ones — no restart")
@click.option("--publish-weights", is_flag=True,
              help="announce this node's params as DHT pieces for joiners")
@click.option("--from-mesh", is_flag=True,
              help="fetch weights from mesh providers via the DHT "
                   "(zero local checkpoint)")
@_common_opts
def serve_tpu(model, checkpoint, lora, mesh_shape, attention, quantize,
              kv_quant, spec_tokens, drafter, adapters, max_adapters,
              publish_weights, from_mesh, **kw):
    """Serve a model on TPU via the jit engine (the flagship entrypoint)."""
    _serve(
        "tpu", model, checkpoint=checkpoint, lora=lora, mesh_shape=mesh_shape,
        attention=attention, quantize=quantize, kv_quant=kv_quant,
        spec_tokens=spec_tokens, drafter=drafter, adapters=adapters,
        max_adapters=max_adapters,
        publish_weights=publish_weights, from_mesh=from_mesh, **kw
    )


@cli.command("serve-ollama")
@click.option("--model", required=True)
@click.option("--ollama-host", default=None, envvar="OLLAMA_HOST")
@_common_opts
def serve_ollama(model, ollama_host, **kw):
    """Proxy a local Ollama daemon into the mesh."""
    _serve("ollama", model, ollama_host=ollama_host, **kw)


@cli.command("serve-hf-remote")
@click.option("--model", required=True)
@_common_opts
def serve_hf_remote(model, **kw):
    """Proxy the HF serverless Inference API into the mesh."""
    _serve("hf_remote", model, **kw)


@cli.command("serve-stage")
@click.option("--model", required=True,
              help="model name or config key; 'auto' derives the "
                   "architecture from --checkpoint's config.json")
@click.option("--n-stages", type=int, default=None,
              help="preload this stage now (otherwise wait for part_load)")
@click.option("--stage", type=int, default=0, help="0-based stage index")
@click.option("--checkpoint", default=None, help="local checkpoint dir")
@click.option("--max-seq-len", type=int, default=2048)
@click.option("--quantize", type=click.Choice(["none", "int8"]), default="none",
              help="weight-only int8 of THIS stage's slice (halves its HBM)")
@_common_opts
def serve_stage(model, n_stages, stage, checkpoint, max_seq_len, quantize, **kw):
    """Host a pipeline-stage worker (layers [a, b) of a model).

    A coordinator peer drives generation across stage workers via the
    task protocol (part_load / part_forward — meshnet/pipeline.py); with
    --n-stages the stage loads immediately, otherwise the node waits for
    a coordinator's part_load."""
    from .meshnet.runtime import run_p2p_node
    from .utils import enable_compile_cache

    _setup_logging()
    enable_compile_cache()
    cfg = _apply_common_cfg(load_config(), kw)

    async def main():
        import functools

        from .engine.stage_runner import StageRunner

        preload = None
        if n_stages is not None:
            loop = asyncio.get_running_loop()
            preload = await loop.run_in_executor(
                None,
                functools.partial(
                    StageRunner,
                    model,
                    n_stages=n_stages,
                    stage=stage,
                    checkpoint_path=checkpoint,
                    max_seq_len=max_seq_len,
                    dtype=cfg.dtype,
                    quantize=quantize,
                ),
            )
        await run_p2p_node(
            backend=None,
            model=model,
            cfg=cfg,
            bootstrap=kw.get("bootstrap"),
            stage_runner=preload,
            tunnel=kw.get("tunnel"),
        )

    try:
        asyncio.run(main())
    except KeyboardInterrupt:
        click.echo("shutting down")


@cli.command("serve-pipeline")
@click.option("--model", required=True, help="model name or config key")
@click.option("--stage-peers", required=True,
              help="comma-separated ws:// addrs of serve-stage workers, "
                   "in stage order")
@click.option("--checkpoint", default=None,
              help="checkpoint dir readable by the WORKERS (part_load path)")
@click.option("--max-seq-len", type=int, default=2048)
@click.option("--max-batch", type=int, default=8,
              help="continuous-batching rows in the pipeline session")
@click.option("--microbatches", default="auto", callback=_microbatches_arg,
              help="'auto' (a compute-vs-hop depth from gossiped stage "
                   "timings on distinct hosts, legacy 2 without telemetry, "
                   "1 on a shared host) or an int >= 1; >1 runs that many "
                   "free-running microbatch groups whose chains interleave "
                   "across stages (costs proportionally more hops)")
@click.option("--quantize", type=click.Choice(["none", "int8"]), default="none",
              help="each stage int8-quantizes its slice at part_load")
@_common_opts
def serve_pipeline(model, stage_peers, checkpoint, max_seq_len,
                   max_batch, microbatches, quantize, **kw):
    """Coordinate a model SPLIT ACROSS stage workers and serve it as a
    normal mesh service (BASELINE config 4: layers [0,L/2) on one peer,
    [L/2,L) on another; activations hop as binary tensor frames).

    Start workers first (`serve-stage`), then this coordinator:
    part_load is pushed to every worker, and the chained generation is
    announced like any other model — gateway /chat, mesh gen_request,
    and streaming all work unchanged."""
    from .meshnet.pipeline import PipelineCoordinator
    from .meshnet.runtime import run_p2p_node
    from .services.pipeline import PipelineService

    _setup_logging()
    cfg = _apply_common_cfg(load_config(), kw)
    addrs = [a.strip() for a in stage_peers.split(",") if a.strip()]
    if not addrs:
        raise click.ClickException("no stage peers given")

    async def main():
        import asyncio as _asyncio

        async def setup(node):
            # dial the workers in stage order; peer ids come from hello
            peer_ids = []
            for addr in addrs:
                if not await node.connect_bootstrap(addr):
                    raise RuntimeError(f"cannot reach stage worker {addr}")
            for _ in range(100):
                peer_ids = [node.peer_for_addr(a) for a in addrs]
                if all(peer_ids):
                    break
                await _asyncio.sleep(0.1)
            if not all(peer_ids):
                raise RuntimeError(f"stage workers not identified: {addrs}")
            coordinator = PipelineCoordinator(
                node, model, stage_peers=peer_ids,
                max_seq_len=max_seq_len, dtype=cfg.dtype, quantize=quantize,
            )
            infos = await coordinator.load(checkpoint_path=checkpoint)
            for i, info in enumerate(infos):
                click.echo(f"stage {i} on {peer_ids[i]}: layers {info.get('layers')}")
            svc = PipelineService(
                coordinator, _asyncio.get_running_loop(), model,
                price_per_token=cfg.price_per_token,
                max_new_tokens=cfg.max_new_tokens,
                max_batch=max_batch, n_microbatches=microbatches,
                checkpoint_path=checkpoint,
            )
            await node.announce_service(svc)
            click.echo(f"pipeline model {model} serving; join link: {node.join_link()}")

        await run_p2p_node(
            backend=None, model=model, cfg=cfg,
            bootstrap=kw.get("bootstrap"), post_start=setup,
            tunnel=kw.get("tunnel"),
        )

    try:
        asyncio.run(main())
    except KeyboardInterrupt:
        click.echo("shutting down")


@cli.command("serve-fake")
@click.option("--model", default="fake-model")
@_common_opts
def serve_fake(model, **kw):
    """Serve a deterministic fake backend (testing/demo)."""
    _serve("fake", model, **kw)


@cli.command("serve-web")
@click.option("--seeds", default="", help="comma-separated ws:// node addrs")
@click.option("--port", type=int, default=4001, help="HTTP port for the web UI/API")
@click.option("--host", default="0.0.0.0")
def serve_web(seeds, port, host):
    """Run the browser-facing web gateway (the reference's Express/React
    tier, rebuilt on aiohttp + a static UI — bee2bee_tpu/web/)."""
    _setup_logging()

    async def main():
        from .registry import RegistryClient
        from .web import MeshBridge, start_web_gateway

        bridge = MeshBridge([s.strip() for s in seeds.split(",") if s.strip()])
        await bridge.start()
        registry = RegistryClient()
        runner = await start_web_gateway(
            bridge, host, port, registry=registry if registry.enabled else None
        )
        click.echo(f"web gateway: http://{host}:{port} (seeds: {bridge.seeds or '-'})")
        try:
            while True:
                await asyncio.sleep(3600)
        finally:
            await runner.cleanup()
            await bridge.stop()

    try:
        asyncio.run(main())
    except KeyboardInterrupt:
        click.echo("shutting down")


@cli.command()
@click.option("--bootstrap", default=None, help="set the default bootstrap url")
def register(bootstrap):
    """One-shot registry upsert + config update (reference __main__.py:78-123)."""
    _setup_logging()
    cfg = load_config()
    if bootstrap:
        cfg.bootstrap_url = bootstrap
        save_config(cfg)
        click.echo(f"bootstrap set to {bootstrap}")

    from .registry import RegistryClient

    client = RegistryClient()
    if not client.enabled:
        click.echo("registry disabled (no SUPABASE_URL/ANON_KEY or BEE2BEE_ENTRYPOINT)")
        return

    async def one_shot():
        from .meshnet.node import P2PNode

        node = P2PNode(host="127.0.0.1", port=0)
        await node.start()
        try:
            ok = await client.sync_node(node)
            click.echo(f"registry sync: {'ok' if ok else 'failed'}")
        finally:
            await node.stop()

    asyncio.run(one_shot())


@cli.command()
@click.option("--model", default="tiny-gpt2", help="model config name")
@click.option("--data", "data_path", required=True, type=click.Path(exists=True),
              help="text file (blank-line-separated documents)")
@click.option("--steps", default=100, help="training steps")
@click.option("--batch-size", default=8)
@click.option("--seq-len", default=128)
@click.option("--lr", default=3e-4)
@click.option("--ckpt-dir", default=None, help="checkpoint directory (resume if present)")
@click.option("--ckpt-every", default=50,
              help="steps between checkpoints (0 = only at the end)")
@click.option("--mesh-shape", default="", help='e.g. "data:2,model:4"')
@click.option("--coordinator", default=None, envvar="BEE2BEE_COORDINATOR",
              help="multi-host: host:port of process 0 (jax.distributed); "
                   "run the SAME command on every host")
@click.option("--num-hosts", type=int, default=1, envvar="BEE2BEE_NUM_HOSTS")
@click.option("--host-id", type=int, default=0, envvar="BEE2BEE_HOST_ID")
@click.option("--zero1", is_flag=True,
              help="shard optimizer state over the data axis (ZeRO-1): "
                   "saves ~2x params of HBM per replica")
@click.option("--checkpoint", "base_ckpt", default=None,
              help="base checkpoint dir (HF or native) to start from — "
                   "required context for --lora-rank finetuning")
@click.option("--lora-rank", type=int, default=0,
              help=">0: LoRA finetuning — train rank-r adapters over the "
                   "frozen base instead of full weights (train/lora.py)")
@click.option("--lora-alpha", type=float, default=16.0)
@click.option("--lora-targets", default="wq,wv",
              help="comma list from wq,wk,wv,wo,w_gate,w_up,w_down")
@click.option("--lora-out", default="lora_adapters.npz",
              help="where the trained adapters land (serve with "
                   "serve-tpu --lora PATH)")
def train(model, data_path, steps, batch_size, seq_len, lr, ckpt_dir, ckpt_every,
          mesh_shape, coordinator, num_hosts, host_id, zero1, base_ckpt,
          lora_rank, lora_alpha, lora_targets, lora_out):
    """Train a causal LM on a local text corpus (checkpoint/resume-able).

    The SPMD realization of the reference's per-layer WS training protocol
    (reference node.py:94-182). Multi-host: every host runs this same
    command with --coordinator host0:port --num-hosts N --host-id i; the
    mesh spans all hosts' chips, each host feeds its batch shard, and
    gradients ride XLA collectives over ICI/DCN (parallel/multihost.py)."""
    _setup_logging()
    if coordinator:
        # must run BEFORE anything touches the jax backend
        from .parallel.multihost import init_multihost

        init_multihost(coordinator, num_processes=num_hosts, process_id=host_id)
    from .utils import enable_compile_cache

    enable_compile_cache()
    from .datasets import PreprocessConfig, from_text_file
    from .engine.tokenizer import ByteTokenizer
    from .models.config import get_config
    from .train.trainer import TrainConfig, Trainer

    cfg = get_config(model)
    tcfg = TrainConfig(learning_rate=lr, total_steps=steps, zero1=zero1)
    mesh = None
    if mesh_shape:
        from .config import parse_mesh_shape
        from .parallel import MeshSpec, build_mesh

        mesh = build_mesh(MeshSpec.from_dict(parse_mesh_shape(mesh_shape)))
    elif coordinator:
        # multi-host without an explicit shape: mesh=None would make every
        # host run an identical independent single-device job (and race on
        # the checkpoint dir) — default to data-parallel over ALL hosts'
        # devices instead
        import jax

        from .parallel import MeshSpec, build_mesh

        mesh = build_mesh(MeshSpec(data=len(jax.devices())))
        click.echo(f"multi-host: defaulting mesh to data:{len(jax.devices())}")

    data = from_text_file(
        data_path, ByteTokenizer(cfg.vocab_size),
        PreprocessConfig(seq_len=seq_len, batch_size=batch_size, shuffle_seed=0),
    )
    if data.n_batches == 0:
        raise click.ClickException("corpus too small for one batch")

    lcfg = None
    if lora_rank > 0:
        # config errors (bad targets for THIS model) must surface before
        # the multi-GB base checkpoint load below
        from .train.lora import LoraConfig, validate_targets

        try:
            lcfg = LoraConfig(rank=lora_rank, alpha=lora_alpha,
                              targets=tuple(lora_targets.split(",")))
            validate_targets(cfg, lcfg)
        except ValueError as e:
            raise click.ClickException(str(e))
        if ckpt_dir or zero1:
            # fail loudly AND before the multi-GB base load below:
            # discovering after a 5000-step run (or a minutes-long load)
            # that --ckpt-dir did nothing is worse than re-running
            raise click.ClickException(
                "--ckpt-dir/--zero1 do not apply to LoRA runs; adapters "
                "are checkpointed to --lora-out every --ckpt-every steps"
            )

    base_params = None
    if base_ckpt:
        import jax.numpy as jnp

        from .models.loader import load_checkpoint

        # the trainer's master-param dtype, NOT the serving default (bf16
        # masters round away ~1e-4-relative Adam updates — loss plateaus)
        base_params = load_checkpoint(
            base_ckpt, cfg, dtype=jnp.dtype(tcfg.param_dtype)
        )

    if lora_rank > 0:
        from .train.lora import LoraTrainer, save_adapters

        if base_params is None:
            from .models import core as _core

            import jax as _jax

            click.echo("warning: --lora-rank without --checkpoint trains "
                       "adapters over a RANDOM base (test runs only)")
            base_params = _core.init_params(cfg, _jax.random.key(0))
        ltr = LoraTrainer(cfg, base_params, lcfg, tcfg, mesh=mesh)
        it = data.repeat()
        while int(ltr.state.step) < steps:
            metrics = ltr.train_step(next(it))
            s = int(ltr.state.step)
            if s % 10 == 0 or s == steps:
                click.echo(f"step {s:5d} loss {metrics['loss']:.4f} "
                           f"acc {metrics['accuracy']:.3f}")
            if ckpt_every > 0 and s % ckpt_every == 0 and s < steps:
                save_adapters(lora_out, ltr.adapters, lcfg)
        save_adapters(lora_out, ltr.adapters, lcfg)
        click.echo(f"adapters -> {lora_out} (serve: bee2bee-tpu serve-tpu "
                   f"--model {model} --lora {lora_out})")
        return

    ckpt = None
    trainer = Trainer(cfg, tcfg, mesh=mesh, params=base_params)
    if ckpt_dir:
        from .train.checkpoint import TrainCheckpointer

        ckpt = TrainCheckpointer(ckpt_dir)
        if ckpt.latest_step() is not None:
            trainer.state = ckpt.restore(cfg, tcfg, mesh=mesh)
            click.echo(f"resumed from step {trainer.step}")

    it = data.repeat()
    while trainer.step < steps:
        metrics = trainer.train_step(next(it))
        if trainer.step % 10 == 0 or trainer.step == steps:
            click.echo(
                f"step {trainer.step:5d} loss {metrics['loss']:.4f} "
                f"acc {metrics['accuracy']:.3f}"
            )
        if ckpt and (
            (ckpt_every > 0 and trainer.step % ckpt_every == 0)
            or trainer.step == steps
        ):
            ckpt.save(trainer.state, cfg, tcfg)
    if ckpt:
        ckpt.close()


@cli.command("export")
@click.option("--model", required=True, help="model name or config key")
@click.option("--checkpoint", default=None,
              help="source checkpoint dir (HF or native); random init if omitted")
@click.option("--out", "out_dir", required=True, help="output directory")
@click.option("--format", "fmt", type=click.Choice(["hf", "native"]), default="hf",
              help="hf: safetensors + config.json any transformers stack "
                   "loads; native: content-addressed pieces + manifest")
@click.option("--dtype", default="float32",
              help="export dtype (float32/float16/bfloat16)")
def export_cmd(model, checkpoint, out_dir, fmt, dtype):
    """Export a model checkpoint to an interchange format.

    The TPU-native analogue of the reference's TorchScript/ONNX export
    (reference hf.py:139-158): torch graph formats make no sense for a
    jax stack, so the interchange surface is HF-layout safetensors
    (loadable by torch/transformers) or the native piece format used for
    mesh weight distribution."""
    _setup_logging()
    import jax
    import jax.numpy as jnp

    from .models import core, get_config
    from .models.export import export_hf
    from .models.loader import load_checkpoint, save_native

    cfg = get_config(model)
    if checkpoint:
        params = load_checkpoint(checkpoint, cfg, dtype=jnp.float32)
    else:
        click.echo("no --checkpoint: exporting random-init params")
        params = core.init_params(cfg, jax.random.key(0), dtype=jnp.float32)
    if fmt == "hf":
        out = export_hf(params, cfg, out_dir, dtype=dtype)
    else:
        if dtype != "float32":  # honor --dtype for native pieces too
            params = jax.tree.map(lambda a: a.astype(jnp.dtype(dtype)), params)
        save_native(params, cfg, out_dir)
        out = out_dir
    click.echo(f"exported {cfg.name} ({fmt}) -> {out}")


@cli.command("nat-status")
@click.option("--port", default=4003, help="port to attempt forwarding for")
@click.option("--forward/--no-forward", default=False,
              help="actually create a mapping (touches the router)")
def nat_status(port, forward):
    """NAT diagnostics: gateway, public IP, NAT type, optional forward
    (reference nat.py:493-561's status table)."""
    _setup_logging()
    from . import nat
    from .stun import STUNClient

    click.echo(f"lan ip:     {nat.get_lan_ip()}")
    click.echo(f"gateway:    {nat.get_gateway_ip()}")
    click.echo(f"public ip:  {nat.get_public_ip()}")
    click.echo(f"nat type:   {STUNClient().detect_nat_type()}")
    if forward:
        mapping = nat.auto_forward_port(port)
        click.echo(
            f"forward:    ok={mapping.ok} method={mapping.method} "
            f"external={mapping.public_ip}:{mapping.external_port} {mapping.detail}"
        )


@cli.command()
def info():
    """Show devices, mesh defaults, and config."""
    import jax

    cfg = load_config()
    click.echo(f"version: {__version__}")
    click.echo(f"devices: {jax.devices()}")
    click.echo(f"config: {cfg.to_dict()}")


if __name__ == "__main__":
    cli()
