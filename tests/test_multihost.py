"""REAL multi-process SPMD test: two localhost processes (4 virtual CPU
devices each) join one jax.distributed cluster, form a single 8-device
mesh, and take a dp2 x sp2 x tp2 train step — the multi-host path the
reference approximates with per-layer WebSocket hops (reference
node.py:94-182), done the XLA way. The per-process losses must agree
with each other AND with a single-process 8-device run."""

import json
import os
import socket
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent

WORKER = textwrap.dedent(
    """
    import json, sys
    sys.path.insert(0, {repo!r})
    import numpy as np

    coordinator, pid = sys.argv[1], int(sys.argv[2])

    from bee2bee_tpu.parallel.multihost import (
        global_array, global_mesh, init_multihost, process_mesh_info,
    )

    devices = init_multihost(coordinator, num_processes=2, process_id=pid)
    info = process_mesh_info()
    assert info["process_count"] == 2, info
    assert info["global_devices"] == 8, info

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from bee2bee_tpu.models import get_config
    from bee2bee_tpu.parallel import MeshSpec
    from bee2bee_tpu.train import TrainConfig, make_train_state, make_train_step

    cfg = get_config("tiny-llama")
    tcfg = TrainConfig(learning_rate=1e-3, param_dtype="float32")
    mesh = global_mesh(MeshSpec(data=2, model=2, seq=2))

    state = make_train_state(cfg, tcfg, jax.random.key(0), mesh=mesh)
    step = make_train_step(cfg, tcfg, mesh=mesh)

    ids_global = np.asarray(
        np.random.default_rng(0).integers(3, cfg.vocab_size, (4, 16)), np.int32
    )
    # every host holds the same global batch; each materializes its shards
    batch = {{"input_ids": global_array(ids_global, mesh, P("data", "seq"))}}
    state, metrics = step(state, batch)
    print(json.dumps({{"pid": pid, "loss": float(metrics["loss"])}}), flush=True)
    """
).format(repo=str(REPO))


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# The minimal cross-process collective the train step's device_put path
# hits first (multihost_utils.broadcast_one_to_all). Some jaxlib CPU
# builds accept jax.distributed.initialize but then refuse the actual
# computation with "Multiprocess computations aren't implemented on the
# CPU backend" — a box-capability gap, not a product bug, so the full
# test SKIPS typed instead of burning a tier-1 F on it.
_PROBE = textwrap.dedent(
    """
    import sys
    import jax
    jax.distributed.initialize(sys.argv[1], num_processes=2,
                               process_id=int(sys.argv[2]))
    import jax.numpy as jnp
    from jax.experimental import multihost_utils
    multihost_utils.broadcast_one_to_all(jnp.zeros((), jnp.float32))
    print("multihost-ok")
    """
)

_PROBE_VERDICT: list = []  # memoized [reason-or-None]


def _multihost_gap() -> str | None:
    """None when two-process collectives work here; else the typed reason
    to skip (probed once per session, ~seconds either way)."""
    if _PROBE_VERDICT:
        return _PROBE_VERDICT[0]
    port = _free_port()
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
    }
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _PROBE, f"127.0.0.1:{port}", str(i)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True,
        )
        for i in range(2)
    ]
    reason = None
    for p in procs:
        try:
            out, err = p.communicate(timeout=90)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            reason = "multihost probe timed out (coordination never settled)"
            break
        if p.returncode != 0 and reason is None:
            tail = [ln for ln in err.strip().splitlines() if ln.strip()]
            reason = (
                "two-process collectives unavailable on this box: "
                + (tail[-1][-200:] if tail else f"probe rc={p.returncode}")
            )
    _PROBE_VERDICT.append(reason)
    return reason


def test_two_process_global_mesh_train_step(tmp_path):
    gap = _multihost_gap()
    if gap:
        pytest.skip(gap)
    port = _free_port()
    script = tmp_path / "worker.py"
    script.write_text(WORKER)
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
    }
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), f"127.0.0.1:{port}", str(i)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        )
        for i in range(2)
    ]
    outs = []
    for p in procs:
        try:
            out, err = p.communicate(timeout=240)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        assert p.returncode == 0, f"worker failed:\n{err[-3000:]}"
        outs.append(json.loads(out.strip().splitlines()[-1]))

    losses = {o["pid"]: o["loss"] for o in outs}
    assert set(losses) == {0, 1}
    # SPMD: every process computes the same global loss
    assert abs(losses[0] - losses[1]) < 1e-6, losses

    # and it matches a single-process 8-device run of the same step
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    import numpy as np

    from bee2bee_tpu.models import get_config
    from bee2bee_tpu.parallel import MeshSpec, build_mesh
    from bee2bee_tpu.train import TrainConfig, make_train_state, make_train_step

    cfg = get_config("tiny-llama")
    tcfg = TrainConfig(learning_rate=1e-3, param_dtype="float32")
    mesh = build_mesh(MeshSpec(data=2, model=2, seq=2))
    state = make_train_state(cfg, tcfg, jax.random.key(0), mesh=mesh)
    step = make_train_step(cfg, tcfg, mesh=mesh)
    ids = jnp.asarray(
        np.random.default_rng(0).integers(3, cfg.vocab_size, (4, 16)), jnp.int32
    )
    _, metrics = step(state, {"input_ids": ids})
    assert abs(float(metrics["loss"]) - losses[0]) < 1e-5
