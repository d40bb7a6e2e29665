"""On-chip persistent-world gate (opt-in, real TPU required).

Run with ``RLA_TPU_WORKER_PLATFORM=tpu`` and the driver left on the
default CPU test platform (on the chip host: through the chip tool):

    RLA_TPU_WORKER_PLATFORM=tpu python -m pytest tests/test_tpu_world.py -q

All other world-persistence evidence is CPU-gloo
(``test_agent.py::test_world_persists_across_entry_points``); this is
the one place the TPU *runtime claim* is exercised where a second claim
could actually conflict — the worker owns the chip for the whole
fit→test→predict span while the driver stays on CPU, mirroring the
reference's actors holding their GPUs from setup to teardown
(reference: ray_lightning/ray_ddp.py:99-121).  A respawn between entry
points would re-claim the device; ship-once reuse proves the dataset
was shipped to the worker once.
"""

import os

import numpy as np
import pytest

from ray_lightning_accelerators_tpu.runtime.agent import HostAgent

# conftest.py pops the var out of the ambient environment (so it cannot
# rewrite every other fan-out test's worker platform) and stashes it for
# this module to re-apply inside its own test scope
from tests.conftest import WORKER_PLATFORM_STASH as _WORKER_PLATFORM

pytestmark = pytest.mark.skipif(
    _WORKER_PLATFORM != "tpu",
    reason="needs RLA_TPU_WORKER_PLATFORM=tpu and a real chip")


def test_single_chip_world_persists_across_entry_points(tmp_path,
                                                        monkeypatch):
    monkeypatch.setenv("RLA_TPU_WORKER_PLATFORM", _WORKER_PLATFORM)
    from ray_lightning_accelerators_tpu import (Callback, DataLoader,
                                                HorovodRayAccelerator,
                                                Trainer)
    from ray_lightning_accelerators_tpu.data.loader import ArrayDataset
    from tests.utils import BoringModel

    class WorkerInfoCb(Callback):
        """Runs worker-side; records the worker's pid and backend into
        the metrics the driver re-hydrates."""

        def _stamp(self, trainer):
            import jax
            trainer.callback_metrics["worker_pid"] = float(os.getpid())
            trainer.callback_metrics["worker_on_tpu"] = float(
                jax.default_backend() == "tpu")

        def on_fit_end(self, trainer, module):
            self._stamp(trainer)

        def on_test_end(self, trainer, module):
            self._stamp(trainer)

    agent = HostAgent(port=0, bind="127.0.0.1")
    agent.serve_in_background()
    try:
        x = np.random.default_rng(0).normal(size=(64, 32)).astype(
            "float32")

        def loader():
            return DataLoader(ArrayDataset(x), batch_size=8,
                              shuffle=False)

        model = BoringModel()
        trainer = Trainer(max_epochs=1, precision="bf16", seed=0,
                          enable_checkpointing=False,
                          callbacks=[WorkerInfoCb()],
                          accelerator=HorovodRayAccelerator(
                              num_hosts=1, num_slots=1,
                              agents=[f"127.0.0.1:{agent.port}"]),
                          default_root_dir=str(tmp_path))
        trainer.fit(model, loader())
        assert trainer.callback_metrics["worker_on_tpu"] == 1.0
        fit_pid = trainer.callback_metrics["worker_pid"]
        assert fit_pid != float(os.getpid())  # really ran in the worker
        assert model.params is not None

        trainer.test(model, loader())
        assert trainer.callback_metrics["worker_on_tpu"] == 1.0
        assert trainer.callback_metrics["worker_pid"] == fit_pid

        preds = trainer.predict(model, loader())
        assert sum(np.shape(p)[0] for p in preds) == len(x)

        # the chip-holding worker spawned exactly once for the whole
        # fit -> test -> predict span (no re-claim between entry points)
        assert agent.spawn_count == 1
        stats = trainer._world.ship_stats
        assert stats["sent"] >= 1 and stats["reused"] >= 1, stats

        # teardown releases the world -- and with it the device claim --
        # so a fresh world (fresh claim) can form afterwards
        trainer.teardown()
        assert trainer._world is None
    finally:
        agent.shutdown()
