"""Train library: JaxTrainer, session, checkpoints, fault tolerance.

(reference surfaces: python/ray/train/tests/test_data_parallel_trainer.py,
test_session.py, air/tests/test_checkpoints.py.)
"""

import os

import numpy as np
import pytest

import ray_tpu
from ray_tpu.train import (
    Checkpoint,
    CheckpointConfig,
    FailureConfig,
    JaxTrainer,
    RunConfig,
    ScalingConfig,
)


def test_checkpoint_dict_dir_roundtrip(tmp_path):
    ck = Checkpoint.from_dict({"w": [1, 2, 3], "step": 7})
    d = ck.to_directory(str(tmp_path / "ck"))
    back = Checkpoint.from_directory(d)
    assert back.to_dict() == {"w": [1, 2, 3], "step": 7}


def test_single_worker_train(ray_start_regular, tmp_path):
    def loop(config):
        from ray_tpu import train

        assert train.get_world_size() == 1
        assert train.get_world_rank() == 0
        for step in range(3):
            train.report({"loss": 1.0 / (step + 1), "step": step})

    trainer = JaxTrainer(
        loop,
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(name="t1", storage_path=str(tmp_path)),
    )
    result = trainer.fit()
    assert result.error is None
    assert len(result.metrics_history) == 3
    assert result.metrics["step"] == 2


def test_multi_worker_allreduce_and_checkpoint(ray_start_regular, tmp_path):
    def loop(config):
        import numpy as np

        from ray_tpu import train
        from ray_tpu.util import collective

        ws = train.get_world_size()
        rank = train.get_world_rank()
        group = os.environ.get("RAYTPU_ACTIVE_GROUP")  # not set; use default name
        # the backend pre-joined a group; find it via the session env
        # (workers store it in the collective registry)
        from ray_tpu.util.collective import collective as col_mod

        group_name = next(iter(col_mod._groups))
        total = collective.allreduce(np.array([float(rank + 1)]), group_name)
        if rank == 0:
            train.report(
                {"sum": float(total[0])},
                checkpoint=Checkpoint.from_dict({"rank_sum": float(total[0])}),
            )
        else:
            train.report({"sum": float(total[0])})

    trainer = JaxTrainer(
        loop,
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(name="t2", storage_path=str(tmp_path)),
    )
    result = trainer.fit()
    assert result.error is None
    assert result.metrics["sum"] == 3.0  # 1 + 2
    assert result.checkpoint is not None
    assert result.checkpoint.to_dict()["rank_sum"] == 3.0


def test_dataset_sharding(ray_start_regular, tmp_path):
    def loop(config):
        from ray_tpu import train

        shard = train.get_dataset_shard("train")
        train.report({"shard_sum": sum(shard)})

    trainer = JaxTrainer(
        loop,
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(name="t3", storage_path=str(tmp_path)),
        datasets={"train": list(range(10))},
    )
    result = trainer.fit()
    assert result.error is None
    # rank 0 gets 0,2,4,6,8
    assert result.metrics["shard_sum"] == 20


def test_failure_restart_from_checkpoint(ray_start_regular, tmp_path):
    marker = tmp_path / "crashed_once"

    def loop(config):
        from ray_tpu import train

        start = 0
        ck = train.get_checkpoint()
        if ck is not None:
            start = ck.to_dict()["step"] + 1
        for step in range(start, 4):
            train.report(
                {"step": step}, checkpoint=Checkpoint.from_dict({"step": step})
            )
            if step == 1 and not os.path.exists(config["marker"]):
                open(config["marker"], "w").close()
                raise RuntimeError("injected failure")

    trainer = JaxTrainer(
        loop,
        train_loop_config={"marker": str(marker)},
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(
            name="t4",
            storage_path=str(tmp_path),
            failure_config=FailureConfig(max_failures=2),
        ),
    )
    result = trainer.fit()
    assert result.error is None
    # resumed from step 1's checkpoint: steps 2 and 3 ran after restart
    assert result.metrics["step"] == 3
    assert result.checkpoint.to_dict()["step"] == 3


def test_failure_exhausts_retries(ray_start_regular, tmp_path):
    def loop():
        raise ValueError("always broken")

    trainer = JaxTrainer(
        loop,
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(name="t5", storage_path=str(tmp_path)),
    )
    result = trainer.fit()
    assert result.error is not None


def test_checkpoint_retention(ray_start_regular, tmp_path):
    def loop(config):
        from ray_tpu import train

        for step in range(5):
            train.report(
                {"acc": step}, checkpoint=Checkpoint.from_dict({"step": step})
            )

    trainer = JaxTrainer(
        loop,
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(
            name="t6",
            storage_path=str(tmp_path),
            checkpoint_config=CheckpointConfig(
                num_to_keep=2, checkpoint_score_attribute="acc"
            ),
        ),
    )
    result = trainer.fit()
    assert result.error is None
    kept = sorted(p for p in os.listdir(tmp_path / "t6") if p.startswith("checkpoint"))
    assert len(kept) == 2
    assert result.checkpoint.to_dict()["step"] == 4


def test_jax_distributed_multiprocess_bringup(ray_start_regular):
    """JaxConfig(init_jax_distributed=True): two worker processes join one
    jax.distributed world through the coordinator the backend wires up,
    and a cross-process allgather sees both ranks' contributions (the
    dist.init_process_group parity point, reference train/torch/config.py
    :113)."""
    from ray_tpu.train import JaxTrainer, ScalingConfig
    from ray_tpu.train.backend_executor import JaxConfig

    def loop(config):
        import jax
        import jax.numpy as jnp
        from jax.experimental import multihost_utils

        from ray_tpu.train import session

        assert jax.process_count() == 2
        # global view spans both ranks' local devices
        assert jax.device_count() == 2 * jax.local_device_count()
        mine = jnp.ones((2,)) * (session.get_world_rank() + 1)
        total = float(multihost_utils.process_allgather(mine).sum())
        session.report({"total": total, "rank": session.get_world_rank()})

    trainer = JaxTrainer(
        loop,
        scaling_config=ScalingConfig(num_workers=2),
        backend_config=JaxConfig(init_jax_distributed=True),
    )
    result = trainer.fit()
    assert result.error is None, result.error
    # ranks 1 and 2 each contribute 2 elements: 2*1 + 2*2 = 6
    assert result.metrics["total"] == 6.0


def test_north_star_pp_fsdp_tp_gang_failure_resume(ray_start_regular, tmp_path):
    """The SURVEY §7 step-5/6 composition in one assertion chain
    (VERDICT r3 next #8): gang-schedule a WorkerGroup on a placement
    group, bring up jax.distributed across 2 processes (4 virtual CPU
    devices each), run the composed pp2 x fsdp2 x tp2 train step through
    JaxTrainer, checkpoint the (device-sharded) state each step, KILL a
    worker mid-run, and resume from the checkpoint to completion."""
    import os as _os

    from ray_tpu.train import JaxTrainer, ScalingConfig
    from ray_tpu.train.backend_executor import JaxConfig

    marker = tmp_path / "killed_once"

    def loop(config):
        import dataclasses
        import os

        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.experimental import multihost_utils

        from ray_tpu import train
        from ray_tpu.models.gpt import gpt_nano
        from ray_tpu.models.training import default_optimizer, init_sharded_state
        from ray_tpu.parallel import sharding as shd
        from ray_tpu.parallel.mesh import MeshSpec
        from ray_tpu.parallel.pipeline import make_pp_train_step

        # the gang really is a 2-process SPMD world over 8 global devices
        assert jax.process_count() == 2
        assert jax.device_count() == 8
        cfg = dataclasses.replace(gpt_nano(), num_layers=4, max_seq_len=32)
        mesh = MeshSpec(dp=-1, pp=2, fsdp=2, tp=2).build(jax.devices())
        opt = default_optimizer(1e-3)
        rules = shd.pp_rules()
        batch, seq = 4, 32
        state, shardings = init_sharded_state(
            cfg, mesh, opt, jax.random.PRNGKey(0), (batch, seq), rules=rules
        )
        step = make_pp_train_step(
            cfg, opt, mesh, num_microbatches=2, rules=rules,
            state_shardings_tree=shardings,
        )
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (batch, seq), 0, cfg.vocab_size
        )
        start = 0
        ck = train.get_checkpoint()
        if ck is not None:
            # restore the sharded state: every rank re-shards the host tree
            # onto its mesh slice via the saved shardings
            payload = ck.to_dict()
            start = payload["step"] + 1
            host_params = payload["params"]
            state = dataclasses.replace(
                state,
                params=jax.device_put(host_params, shardings.params),
            )
        with mesh:
            for s in range(start, 4):
                state, metrics = step(state, tokens)
                loss = float(metrics["loss"])
                assert np.isfinite(loss)
                # checkpoint: gather the (tiny) device-sharded params into a
                # replicated host tree so any restarted gang can re-shard it
                # via device_put(shardings) — the dict checkpoint then rides
                # the normal session/CheckpointManager plumbing
                host_params = jax.tree.map(
                    lambda x: np.asarray(
                        multihost_utils.process_allgather(x, tiled=True)
                    ),
                    state.params,
                )
                train.report(
                    {"loss": loss, "step": s},
                    checkpoint=train.Checkpoint.from_dict(
                        {"step": s, "params": host_params}
                    ),
                )
                if (
                    s == 1
                    and train.session.get_world_rank() == 1
                    and not os.path.exists(config["marker"])
                ):
                    open(config["marker"], "w").close()
                    os._exit(1)  # chaos: the worker PROCESS dies mid-gang

    trainer = JaxTrainer(
        loop,
        train_loop_config={"marker": str(marker)},
        scaling_config=ScalingConfig(
            num_workers=2, placement_strategy="PACK",
        ),
        backend_config=JaxConfig(
            init_jax_distributed=True, local_device_count=4
        ),
        run_config=ray_tpu.train.RunConfig(
            name="northstar",
            storage_path=str(tmp_path),
            failure_config=ray_tpu.train.FailureConfig(max_failures=2),
        ),
    )
    result = trainer.fit()
    assert result.error is None, f"north-star run failed: {result.error}"
    assert result.metrics["step"] == 3
    assert _os.path.exists(marker), "the injected kill never fired"
    restored = result.checkpoint.to_dict()
    assert restored["step"] == 3


def test_batch_predictor(ray_start_regular, tmp_path):
    """Checkpoint -> BatchPredictor.predict over a Dataset via an actor
    pool; model loads once per actor (reference: train/batch_predictor.py)."""
    import numpy as np

    from ray_tpu import data as rd
    from ray_tpu.train import BatchPredictor, Predictor

    class LinearPredictor(Predictor):
        def __init__(self, checkpoint, scale=1.0):
            super().__init__(checkpoint)
            payload = checkpoint.to_dict()
            self.w = payload["w"]
            self.b = payload["b"]
            self.scale = scale
            self.loads = payload  # constructed once per actor

        def predict_batch(self, batch):
            x = batch["x"].astype(np.float64)
            return {"pred": (x * self.w + self.b) * self.scale}

    ck = Checkpoint.from_dict({"w": 3.0, "b": 1.0})
    predictor = BatchPredictor.from_checkpoint(ck, LinearPredictor, scale=2.0)
    ds = rd.range(1000, parallelism=4).map_batches(
        lambda b, **_: {"x": b["id"], "key": b["id"]}
    )
    out = predictor.predict(
        ds, batch_size=100, num_actors=2,
        feature_columns=["x"], keep_columns=["key"],
    )
    rows = out.take(1000)
    assert len(rows) == 1000
    for r in rows[:10]:
        assert r["pred"] == (r["key"] * 3.0 + 1.0) * 2.0
