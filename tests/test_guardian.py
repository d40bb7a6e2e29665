"""Numeric anomaly guardian (runtime/guardian.py): traced guard vector,
blame classes, quarantine skip ledger, ElasticRunner rewind loop, and
the serve-tier decode guard.

The acceptance loop for the subsystem: inject ``badbatch@stepK`` numeric
chaos, the in-step guard trips on the readback that was happening
anyway, blame lands on ``data``, the blamed (epoch, batch_idx) window is
quarantined in the rank/restart-deterministic skip ledger, and the
resumed fit skips exactly that window to a clean finish — all on CPU,
no TPU, no timing races.  Chaos specs are claimed through a private
``RLA_TPU_CHAOS_NS`` so retries replay clean; conftest guards the
driver env against leaks regardless.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_lightning_accelerators_tpu import (ArrayDataset, DataLoader,
                                            Callback, ModelCheckpoint,
                                            RayTPUAccelerator, Trainer)
from ray_lightning_accelerators_tpu.runtime import guardian
from ray_lightning_accelerators_tpu.runtime.actors import ActorPool
from ray_lightning_accelerators_tpu.runtime.elastic import ElasticRunner
from ray_lightning_accelerators_tpu.runtime.guardian import (GuardConfig,
                                                             Guardian,
                                                             NumericAnomaly)
from ray_lightning_accelerators_tpu.utils import checkpoint as ckpt_lib

from .utils import BoringModel

pytestmark = pytest.mark.guardian


def _data(rows=64, seed=0):
    return np.random.default_rng(seed).normal(
        size=(rows, 32)).astype(np.float32)


def _trainer(root, guard="auto", **kw):
    kw.setdefault("max_epochs", 1)
    kw.setdefault("precision", "f32")
    kw.setdefault("seed", 0)
    kw.setdefault("enable_checkpointing", False)
    kw.setdefault("enable_progress_bar", False)
    kw.setdefault("log_every_n_steps", 1)
    return Trainer(default_root_dir=str(root), guard=guard, **kw)


# --------------------------------------------------------------------- #
# Traced half (pure jnp, no fit)                                        #
# --------------------------------------------------------------------- #
def test_update_flags_trip_and_freeze_evidence():
    """The guard-state transition: healthy steps fold the EMA, the first
    unhealthy step pins the postmortem fields, later trips keep the
    sticky bit but never overwrite the evidence."""
    cfg = GuardConfig(spike_factor=10.0, ema_decay=0.5, warmup_steps=1,
                      update_ratio_max=0.5)
    g = jnp.asarray(guardian.fresh_state())
    # step 0: healthy — seeds the EMA, arms the warmup counter
    g, m = guardian.update(cfg, g, 0, 1.0, 2.0, 0.1)
    assert float(g[guardian.I_TRIPPED]) == 0.0
    assert float(g[guardian.I_EMA]) == 2.0
    assert float(g[guardian.I_COUNT]) == 1.0
    assert m.shape == (guardian.METRIC_WIDTH,)
    # step 1: gnorm 50 > 10 * EMA(2.0) — the spike flag trips and pins
    g, _ = guardian.update(cfg, g, 1, 1.0, 50.0, 0.1)
    assert float(g[guardian.I_TRIPPED]) == 1.0
    assert float(g[guardian.I_TRIP_STEP]) == 1.0
    assert float(g[guardian.I_FLAG_SPIKE]) == 1.0
    assert float(g[guardian.I_FLAG_LOSS]) == 0.0
    # unhealthy steps never fold into the EMA
    assert float(g[guardian.I_EMA]) == 2.0
    # step 2: NaN loss — sticky stays, but the FIRST trip's evidence wins
    g, _ = guardian.update(cfg, g, 2, float("nan"), 1.0, 0.1)
    assert float(g[guardian.I_TRIP_STEP]) == 1.0
    assert float(g[guardian.I_FLAG_LOSS]) == 0.0


def test_update_names_lone_suspect_replica():
    """A some-but-not-all per-replica badness vector names the suspect;
    every-replica-bad (a poisoned global batch) names nobody."""
    cfg = GuardConfig(warmup_steps=0)
    g = jnp.asarray(guardian.fresh_state())
    bad = jnp.asarray([0.0, 0.0, 1.0, 0.0])
    g, _ = guardian.update(cfg, g, 3, float("nan"), 1.0, 0.0, rank_bad=bad)
    assert float(g[guardian.I_SUSPECT]) == 2.0
    assert float(g[guardian.I_NBAD]) == 1.0
    g2 = jnp.asarray(guardian.fresh_state())
    g2, _ = guardian.update(cfg, g2, 3, float("nan"), 1.0, 0.0,
                            rank_bad=jnp.ones((4,)))
    assert float(g2[guardian.I_SUSPECT]) == -1.0
    assert float(g2[guardian.I_NBAD]) == 4.0


def test_per_replica_bad_flags_nan_and_norm_outlier():
    stacked = {"w": jnp.asarray(np.ones((4, 8), np.float32))}
    assert np.allclose(
        np.asarray(guardian.per_replica_bad(stacked, 10.0)), 0.0)
    poisoned = np.ones((4, 8), np.float32)
    poisoned[2, 0] = np.nan
    bad = np.asarray(guardian.per_replica_bad(
        {"w": jnp.asarray(poisoned)}, 10.0))
    assert bad.tolist() == [0.0, 0.0, 1.0, 0.0]
    spiky = np.ones((4, 8), np.float32)
    spiky[1] *= 1e6  # finite, but 1e6x the replica median norm
    bad = np.asarray(guardian.per_replica_bad(
        {"w": jnp.asarray(spiky)}, 10.0))
    assert bad.tolist() == [0.0, 1.0, 0.0, 0.0]


# --------------------------------------------------------------------- #
# Quarantine ledger (pure host)                                         #
# --------------------------------------------------------------------- #
def test_quarantine_ledger_roundtrip_and_anchor(tmp_path):
    root = str(tmp_path)
    assert guardian.load_quarantine(root) == {"entries": [], "anchor": None}
    guardian.add_quarantine(root, 0, 3, 11, anchor="/ck/a.ckpt")
    guardian.add_quarantine(root, 0, 3, 11)  # idempotent append
    guardian.add_quarantine(root, 1, 5, 21)
    doc = guardian.load_quarantine(root)
    assert len(doc["entries"]) == 2
    assert doc["anchor"] == "/ck/a.ckpt"
    # the skip set is a PURE function of the ledger, per epoch — every
    # rank and every restart computes the identical set
    assert guardian.skip_set(root, 0) == {3}
    assert guardian.skip_set(root, 1) == {5}
    assert guardian.skip_set(root, 2) == set()
    # pruning must protect the anchor whether the ledger sits at the
    # checkpoint dir itself or one directory up
    assert guardian.protected_paths(root) == ["/ck/a.ckpt"]
    assert guardian.protected_paths(
        os.path.join(root, "checkpoints")) == ["/ck/a.ckpt"]
    # releasing the anchor keeps the skip entries — the data is still bad
    guardian.release_anchor(root)
    doc = guardian.load_quarantine(root)
    assert doc["anchor"] is None and len(doc["entries"]) == 2


def test_rewind_anchor_never_selects_unverified(tmp_path):
    """The rewind anchor is ``latest_checkpoint``'s digest walk: a torn
    newest checkpoint is skipped, the older verified one is handed
    over — a rewind must never land on a checkpoint it cannot restore."""
    a = tmp_path / "ckpts" / "epoch=0-step=8.ckpt"
    b = tmp_path / "ckpts" / "epoch=1-step=16.ckpt"
    a.parent.mkdir()
    ckpt_lib.atomic_save({"global_step": 8}, str(a))
    ckpt_lib.atomic_save({"global_step": 16}, str(b))
    os.utime(a, (1, 1))
    os.utime(b, (2, 2))
    g = Guardian(GuardConfig(), str(tmp_path))
    assert g._rewind_anchor() == str(b)
    b.write_bytes(b.read_bytes()[:4])  # torn mid-write
    os.utime(b, (2, 2))
    assert g._rewind_anchor() == str(a)


def test_prune_keeps_quarantine_anchor_alive(tmp_path):
    """``ModelCheckpoint._prune`` must keep the rewind anchor while a
    quarantine is active — evicting it would turn a cheap rewind into a
    cold restart — and may GC it once the anchor is released."""
    root = str(tmp_path)
    ck = tmp_path / "checkpoints"
    ck.mkdir()
    paths = []
    for i in range(3):
        p = ck / f"epoch={i}.ckpt"
        ckpt_lib.atomic_save({"global_step": 8 * (i + 1)}, str(p))
        os.utime(p, (i + 1, i + 1))
        paths.append(p)
    guardian.add_quarantine(root, 0, 2, 5, anchor=str(paths[0]))
    mc = ModelCheckpoint(monitor=None, keep_last_k=1)
    mc.dirpath = str(ck)
    mc._prune()
    assert paths[0].exists()      # the anchor, oldest, survives
    assert not paths[1].exists()  # plain retention victim
    assert paths[2].exists()      # newest of keep_last_k=1
    guardian.release_anchor(root)
    mc._prune()
    assert not paths[0].exists()


# --------------------------------------------------------------------- #
# Fit-level trips: one per blame verdict                                #
# --------------------------------------------------------------------- #
@pytest.mark.chaos
def test_nanloss_trips_typed_with_sdc_blame(tmp_path):
    """``nanloss`` lives only in the compiled step, so the eager blame
    replay runs clean — not data, compression off — the designed verdict
    is a nondeterministic suspected-SDC trip, typed with the postmortem
    embedded in the message."""
    os.environ["RLA_TPU_CHAOS"] = "nanloss@rank0:step3"
    try:
        tr = _trainer(tmp_path)
        with pytest.raises(NumericAnomaly) as ei:
            tr.fit(BoringModel(),
                   DataLoader(ArrayDataset(_data()), batch_size=8))
    finally:
        os.environ.pop("RLA_TPU_CHAOS", None)
    e = ei.value
    assert e.step == 2  # 0-based TrainState.step of the 1-based step 3
    assert e.blame == "sdc"
    assert e.diagnosis["flags"]["loss_nonfinite"]
    assert NumericAnomaly._MARKER in str(e)
    # sdc blame never quarantines data
    assert guardian.load_quarantine(str(tmp_path))["entries"] == []


@pytest.mark.chaos
def test_gradspike_trips_spike_flag(tmp_path):
    os.environ["RLA_TPU_CHAOS"] = "gradspike@rank0:step5"
    try:
        tr = _trainer(tmp_path, guard=GuardConfig(warmup_steps=2))
        with pytest.raises(NumericAnomaly) as ei:
            tr.fit(BoringModel(),
                   DataLoader(ArrayDataset(_data()), batch_size=8))
    finally:
        os.environ.pop("RLA_TPU_CHAOS", None)
    e = ei.value
    assert e.step == 4
    flags = e.diagnosis["flags"]
    assert flags["spike"] or flags["update_ratio"], flags
    assert e.blame == "sdc"  # eager replay reproduces nothing


@pytest.mark.chaos
def test_badbatch_blames_data_and_quarantines(tmp_path):
    """blame=data end to end in one process: the recorded host batch is
    non-finite, the ledger gains the blamed window, and a second fit on
    the same root (claim spent through the namespace) skips exactly that
    batch to a clean finish with deterministic step accounting."""
    ns = tmp_path / "chaos_ns"
    os.environ["RLA_TPU_CHAOS"] = "badbatch@step3"
    os.environ["RLA_TPU_CHAOS_NS"] = str(ns)
    try:
        with pytest.raises(NumericAnomaly) as ei:
            _trainer(tmp_path).fit(
                BoringModel(),
                DataLoader(ArrayDataset(_data()), batch_size=8))
        e = ei.value
        assert e.blame == "data"
        assert (e.step, e.epoch, e.batch_idx) == (2, 0, 2)
        assert guardian.skip_set(str(tmp_path), 0) == {2}
        # resumed fit: the claim token is spent, the quarantined batch is
        # skipped WITHOUT breaking the epoch's batch enumeration
        tr = _trainer(tmp_path)
        tr.fit(BoringModel(),
               DataLoader(ArrayDataset(_data()), batch_size=8))
        assert tr.global_step == 7  # 8 batches - 1 quarantined
        assert np.isfinite(float(tr.callback_metrics["train_loss"]))
        # the skip entries survive the clean finish (the data is still
        # bad); only the prune-protection anchor is released
        doc = guardian.load_quarantine(str(tmp_path))
        assert len(doc["entries"]) == 1 and doc["anchor"] is None
    finally:
        os.environ.pop("RLA_TPU_CHAOS", None)
        os.environ.pop("RLA_TPU_CHAOS_NS", None)


@pytest.mark.chaos
@pytest.mark.collectives
def test_bitflip_under_compressed_dp_names_suspect(tmp_path):
    """SDC blame with a NAMED rank: a single-replica exponent-bit flip in
    the stacked local gradients diverges the per-replica badness vector
    (one replica bad, seven clean) — the signature a poisoned global
    batch can never produce."""
    os.environ["RLA_TPU_CHAOS"] = "bitflip@rank1:step5"
    try:
        tr = _trainer(tmp_path, guard=GuardConfig(warmup_steps=2),
                      accelerator=RayTPUAccelerator(num_workers=8),
                      grad_compression="int8")
        with pytest.raises(NumericAnomaly) as ei:
            tr.fit(BoringModel(),
                   DataLoader(ArrayDataset(_data()), batch_size=8))
    finally:
        os.environ.pop("RLA_TPU_CHAOS", None)
    e = ei.value
    assert e.blame == "sdc"
    assert e.suspect_rank == 1
    assert e.diagnosis["flags"]["grad_norm"] > 0


def test_guard_none_bit_identical_and_guarded_zero_retraces(tmp_path):
    """``guard=None`` must reproduce the pre-guardian trajectory exactly
    (the guard is pure observation), and the guarded fit must add zero
    retraces after its warmup epoch — the flags ride the readback that
    was happening anyway."""
    from ray_lightning_accelerators_tpu.analysis import compile_guard as cg
    cg.install()
    compiles = {"at_epoch_end": None, "fit_end": None}

    class _Window(Callback):
        def on_train_epoch_end(self, trainer, module):
            if trainer.current_epoch == 0:
                compiles["at_epoch_end"] = cg.compile_count()

    def fit(guard, cbs=()):
        tr = _trainer(tmp_path / ("g" if guard else "u"), guard=guard,
                      max_epochs=2, callbacks=list(cbs))
        tr.fit(BoringModel(),
               DataLoader(ArrayDataset(_data()), batch_size=8))
        return float(tr.callback_metrics["train_loss"])

    guarded = fit("auto", cbs=[_Window()])
    compiles["fit_end"] = cg.compile_count()
    unguarded = fit(None)
    assert guarded == unguarded  # bit-identical, not merely close
    assert compiles["fit_end"] == compiles["at_epoch_end"]


# --------------------------------------------------------------------- #
# ElasticRunner: rewind semantics (light bodies, no jax in workers)     #
# --------------------------------------------------------------------- #
def _anomaly_once_body(attempt):
    if attempt == 0:
        from ray_lightning_accelerators_tpu.runtime.guardian import (
            NumericAnomaly)
        raise NumericAnomaly.for_trip(step=5, blame="data", epoch=0,
                                      batch_idx=5)
    return "ok"


def test_runner_rewind_does_not_charge_failure_budget():
    """A tripped guard is a REWIND, not a failure: with max_failures=0 a
    one-shot anomaly still resumes — and the typed postmortem crossed the
    worker pipe intact (wire registry), not as a stringly RemoteError."""
    pool = ActorPool(2)
    charged = []
    try:
        runner = ElasticRunner(pool, max_failures=0,
                               on_failure=lambda a, e: charged.append(e))
        out = runner.run(_anomaly_once_body,
                         args_per_worker=lambda a: [(a,)] * 2)
        assert out == ["ok", "ok"]
        assert runner.attempts_used == 2
        assert charged == []
        (ev,) = runner.anomaly_events
        assert ev["blame"] == "data" and ev["step"] == 5
    finally:
        pool.shutdown()


def _anomaly_same_step_body(attempt):
    from ray_lightning_accelerators_tpu.runtime.guardian import (
        NumericAnomaly)
    raise NumericAnomaly.for_trip(step=7, blame="data", epoch=0,
                                  batch_idx=7)


def test_runner_same_data_step_twice_is_terminal():
    """A data-blamed step that trips again AFTER its window was
    quarantined proves the quarantine did not clear it — retrying cannot
    converge, so the loop refuses instead of burning rewinds."""
    pool = ActorPool(1)
    try:
        runner = ElasticRunner(pool, max_failures=0, max_rewinds=5)
        with pytest.raises(RuntimeError,
                           match="recurred after its data window"):
            runner.run(_anomaly_same_step_body,
                       args_per_worker=lambda a: [(a,)])
        assert runner.attempts_used == 2
    finally:
        pool.shutdown()


def _anomaly_roaming_body(attempt):
    from ray_lightning_accelerators_tpu.runtime.guardian import (
        NumericAnomaly)
    raise NumericAnomaly.for_trip(step=100 + attempt, blame="unknown")


def test_runner_max_rewinds_is_terminal():
    pool = ActorPool(1)
    try:
        runner = ElasticRunner(pool, max_failures=0, max_rewinds=2)
        with pytest.raises(RuntimeError,
                           match=r"tripped the numeric guard 3 times"):
            runner.run(_anomaly_roaming_body,
                       args_per_worker=lambda a: [(a,)])
        assert runner.attempts_used == 3
        assert len(runner.anomaly_events) == 3
    finally:
        pool.shutdown()


def _sdc_once_body(attempt, rank):
    if attempt == 0 and rank == 0:
        from ray_lightning_accelerators_tpu.runtime.guardian import (
            NumericAnomaly)
        raise NumericAnomaly.for_trip(step=9, blame="sdc", suspect_rank=2)
    return ("ok", rank)


def test_runner_sdc_demotes_named_suspect_rank():
    """An SDC verdict with a named rank demotes that rank via the elastic
    shrink path: the retry runs at world-1 without the suspect, floored
    by min_workers, without charging the failure budget."""
    pool = ActorPool(3)
    try:
        runner = ElasticRunner(pool, max_failures=0, allow_shrink=True,
                               min_workers=2)
        out = runner.run(
            _sdc_once_body,
            args_per_worker=lambda a, world: [(a, r)
                                              for r in range(world)])
        assert len(out) == 2
        (shrink,) = runner.shrink_events
        assert shrink["dropped"] == [2] and shrink["blame"] == "sdc"
        assert sorted(w.rank for w in pool.workers) == [0, 1]
    finally:
        pool.shutdown()


# --------------------------------------------------------------------- #
# The acceptance loop: chaos fit under the runner, end to end           #
# --------------------------------------------------------------------- #
def _guarded_fit_body(root):
    """One attempt of a guarded single-process fit (spawned worker; the
    runner's restart is the rewind)."""
    import numpy as np
    from ray_lightning_accelerators_tpu import DataLoader, Trainer
    from ray_lightning_accelerators_tpu.data.loader import ArrayDataset
    from tests.utils import BoringModel
    x = np.random.default_rng(0).normal(size=(64, 32)).astype("float32")
    tr = Trainer(max_epochs=2, precision="f32", seed=0,
                 default_root_dir=root, log_every_n_steps=1,
                 enable_checkpointing=False, enable_progress_bar=False)
    tr.fit(BoringModel(), DataLoader(ArrayDataset(x), batch_size=8),
           ckpt_path="last")
    return (tr.global_step,
            float(np.asarray(tr.callback_metrics["train_loss"])))


@pytest.mark.chaos
def test_elastic_rewind_and_skip_acceptance_loop(tmp_path):
    """End to end: ``badbatch@step3`` trips the guarded fit inside a
    worker, the typed ``NumericAnomaly`` crosses the pipe, the runner
    rewinds WITHOUT charging the failure budget, and the retried fit —
    its chaos claim spent, its quarantine ledger shared through the run
    dir — skips the blamed window to a clean two-epoch finish."""
    root = str(tmp_path / "run")
    os.makedirs(root)
    env = {"RLA_TPU_CHAOS": "badbatch@step3",
           "RLA_TPU_CHAOS_NS": str(tmp_path / "chaos_ns"),
           "JAX_PLATFORMS": "cpu"}
    pool = ActorPool(1, env_per_worker=[env])
    try:
        runner = ElasticRunner(pool, max_failures=0, max_rewinds=2)
        ((steps, loss),) = runner.run(
            _guarded_fit_body, args_per_worker=lambda a: [(root,)])
        assert runner.attempts_used == 2
        (ev,) = runner.anomaly_events
        assert ev["blame"] == "data" and ev["batch_idx"] == 2
        # 2 epochs x 8 batches, minus the one quarantined epoch-0 window
        assert steps == 15
        assert np.isfinite(loss)
        assert guardian.skip_set(root, 0) == {2}
    finally:
        pool.shutdown()


# --------------------------------------------------------------------- #
# Serve-tier decode guard                                               #
# --------------------------------------------------------------------- #
@pytest.mark.serve
def test_serve_decode_guard_fails_single_request_typed():
    """Non-finite decode logits fail ONLY the affected slot's request —
    typed ``NumericAnomaly``, ``numeric_anomalies`` counter bumped — and
    the other in-flight request completes token-identical to a
    standalone generate()."""
    from ray_lightning_accelerators_tpu.models.transformer import (
        GPT, TransformerConfig)
    from ray_lightning_accelerators_tpu.serve import ServeEngine
    model = GPT(TransformerConfig(vocab_size=61, d_model=32, n_heads=2,
                                  d_ff=64, n_layers=2, max_seq_len=32))
    params = model.init_params(jax.random.PRNGKey(0))
    pa = np.asarray([1, 2, 3, 4], np.int32)
    pb = np.asarray([7, 8, 9], np.int32)
    ref_b = np.asarray(model.generate(params, jnp.asarray(pb[None]),
                                      max_new_tokens=6))[0]
    with ServeEngine(model, jax.tree.map(np.asarray, params),
                     max_slots=2, queue_depth=8) as eng:
        real = eng._step
        calls = {"n": 0}

        def chaotic(*a):
            toks, ok, cache = real(*a)
            calls["n"] += 1
            if calls["n"] >= 2:  # slot 0's second decode step onward
                ok = ok.at[0].set(False)
            return toks, ok, cache

        eng._step = chaotic
        ra = eng.submit(pa, 8)
        rb = eng.submit(pb, 6)
        with pytest.raises(NumericAnomaly,
                           match="non-finite logits"):
            ra.result(timeout=300)
        out_b = rb.result(timeout=300)
    np.testing.assert_array_equal(out_b, ref_b)
    snap = eng.stats()
    assert snap["numeric_anomalies"] == 1
    assert snap["completed"] == 1 and snap["failed"] == 1
