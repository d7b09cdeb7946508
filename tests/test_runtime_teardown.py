"""run_p2p_node's teardown (PR 48): a served engine's scheduler thread is
ended before the process leaves, so the interpreter's finalization finds no
thread inside the device runtime (on the chip half the exits of a busy
serve-tpu ended by SIGABRT: PERF.md section 7)."""

import asyncio

from bee2bee_tpu.config import NodeConfig
from bee2bee_tpu.meshnet.runtime import run_p2p_node


class _Engine:
    closed = 0

    def close(self):
        self.closed += 1


async def test_teardown_closes_the_engine_of_every_local_service():
    ready, shutdown = asyncio.Event(), asyncio.Event()
    engine = _Engine()

    async def post_start(node):
        (svc,) = node.local_services.values()
        svc.engine = engine  # what TPUService carries; the fake has none

    task = asyncio.create_task(run_p2p_node(
        backend="fake", model="teardown-model",
        cfg=NodeConfig(host="127.0.0.1", port=0, auto_nat=False),
        serve_api=False, registry_sync=False,
        ready_event=ready, shutdown_event=shutdown, post_start=post_start,
    ))
    await asyncio.wait_for(ready.wait(), 30)
    assert engine.closed == 0
    shutdown.set()
    await asyncio.wait_for(task, 15)
    assert engine.closed == 1
