"""Autotuner (reference: autotuning/autotuner.py:42 ``Autotuner`` +
scheduler.py experiment runner + tuner/{GridSearchTuner,RandomTuner,
ModelBasedTuner} — explores ZeRO stage x micro-batch (x user overrides)
and picks the config maximising throughput).

TPU-native experiment loop: no subprocess launches — each candidate
builds a DeepSpeedEngine on the live mesh, jit-compiles one train step on
tiny-but-representative shapes, and either

* **fast mode** scores with the compiler's cost model
  (``Compiled.cost_analysis()`` flops/bytes — seconds per candidate), or
* **measured mode** times real steps (``samples/sec``),

with a memory-model prefilter (the reference ModelBasedTuner role): ZeRO
stage s on W shards needs ~(2 + 16/W_s) bytes/param of HBM; infeasible
candidates are skipped without compiling. Results land in
``autotuning_results/`` as one JSON record per experiment plus the best
config (reference exps/results layout).
"""

from __future__ import annotations

import itertools
import json
import os
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from deepspeed_tpu.utils.logging import logger

DEFAULT_MICRO_BATCHES = (1, 2, 4, 8)
DEFAULT_STAGES = (0, 1, 2, 3)


def _isolated_worker(payload_bytes: bytes, n_devices: int, platform: str,
                     conn) -> None:
    """Spawned-process entry for one isolated experiment (top-level so the
    spawn context can import it; the heavy state rides in cloudpickle).
    The backend env is pinned BEFORE unpickling — loading the payload
    imports jax."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags and \
            platform == "cpu":
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n_devices}"
        ).strip()
    if platform == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"
    import cloudpickle

    payload = cloudpickle.loads(payload_bytes)
    import jax

    if platform == "cpu":
        jax.config.update("jax_platforms", "cpu")
    from deepspeed_tpu.parallel import groups

    dims = payload["mesh_dims"]
    groups.initialize_mesh(
        pipe_parallel_size=dims["pipe"],
        data_parallel_size=dims["dout"] * dims["data"],
        sequence_parallel_size=dims["seq"],
        expert_parallel_size=dims["expert"],
        model_parallel_size=dims["model"],
        zero_subgroup_size=dims["data"] if dims["dout"] > 1 else 0)
    tuner = payload["tuner"]
    exp = payload["exp"]
    tuner._run_experiment(exp)
    conn.send((exp.metric_val, exp.error))
    conn.close()


class Experiment:
    def __init__(self, name: str, config: Dict[str, Any]):
        self.name = name
        self.config = config
        self.metric_val: Optional[float] = None
        self.error: Optional[str] = None

    def record(self) -> Dict[str, Any]:
        return {"name": self.name, "ds_config": self.config,
                "metric_val": self.metric_val, "error": self.error}


class Autotuner:
    def __init__(self, model, base_config: Dict[str, Any],
                 sample_batch_fn: Callable[[int], Tuple],
                 results_dir: str = "autotuning_results",
                 tuner_type: str = "gridsearch",
                 metric: str = "throughput",
                 micro_batch_sizes: Sequence[int] = DEFAULT_MICRO_BATCHES,
                 zero_stages: Sequence[int] = DEFAULT_STAGES,
                 max_trials: int = 50,
                 steps_per_trial: int = 3,
                 fast: bool = False,
                 hbm_bytes: Optional[float] = None,
                 activation_bytes_per_sample: Optional[float] = None,
                 peak_flops: float = 2e14, peak_bw: float = 8e11,
                 isolate: bool = False, trial_timeout: float = 600.0,
                 seed: int = 0,
                 flops_per_sample: Optional[float] = None):
        """``sample_batch_fn(micro_batch)`` returns the engine-call args
        for one micro batch of that size (the model-info profile run uses
        size 1).

        ``isolate=True`` SPAWNS each experiment into its own process
        (reference autotuning/scheduler.py:430 runs experiments as
        separate launches): a hard crash, native OOM abort, or hang
        (``trial_timeout``) in one candidate prunes that candidate
        instead of killing the whole tune. CPU-mesh tuning only: the
        tuning loop initialises the parent's backend, a chip belongs to
        one process at a time, and a child that needs the chip the
        parent holds fails or hangs — so ``tune()`` refuses ``isolate``
        on a TPU.
        """
        if tuner_type not in ("gridsearch", "random", "model_based"):
            raise ValueError(f"unknown tuner {tuner_type!r}")
        self.model = model
        self.base_config = dict(base_config)
        self.sample_batch_fn = sample_batch_fn
        self.results_dir = results_dir
        self.tuner_type = tuner_type
        self.metric_name = metric
        self.micro_batch_sizes = list(micro_batch_sizes)
        self.zero_stages = list(zero_stages)
        self.max_trials = max_trials
        self.steps_per_trial = steps_per_trial
        self.fast = fast
        self.hbm_bytes = hbm_bytes
        self.activation_bytes_per_sample = activation_bytes_per_sample
        self.peak_flops = peak_flops  # roofline peaks for fast mode
        self.peak_bw = peak_bw
        #: model flops per sample (e.g. FlopsProfiler.get_total_flops /
        #: batch) — gives the model-based tuner a roofline prior
        self.flops_per_sample = flops_per_sample
        self.isolate = isolate
        self.trial_timeout = trial_timeout
        self.rng = np.random.default_rng(seed)
        self.records: List[Experiment] = []
        self._num_params: Optional[int] = None

    # -------------------------------------------------------------- #
    # model info + memory model (reference model_info_profile_run /
    # get_instantiation_memory_required_per_gpu)
    # -------------------------------------------------------------- #
    def model_info(self) -> Dict[str, Any]:
        if self._num_params is None:
            import jax

            from deepspeed_tpu.parallel import groups

            topo = groups.get_topology()
            cfg = {**self.base_config,
                   "train_micro_batch_size_per_gpu": 1,
                   "zero_optimization": {"stage": 0}}
            import deepspeed_tpu

            engine, _, _, _ = deepspeed_tpu.initialize(
                model=self.model, config=cfg, topology=topo)
            engine.initialize_parameters(*self.sample_batch_fn(1))
            self._num_params = sum(
                int(np.prod(l.shape))
                for l in jax.tree.leaves(engine.state["params"]))
        return {"num_params": self._num_params}

    def estimate_state_bytes(self, stage: int, world: int) -> float:
        """HBM bytes/chip for params+master+moments+grads at a ZeRO stage
        (reference memory-per-GPU estimate): compute copy always
        replicated except stage 3; fp32 master+2 moments (12B) sharded
        from stage 1; fp32 grads sharded from stage 2."""
        n = self.model_info()["num_params"]
        p_bytes = 2.0 * n / (world if stage >= 3 else 1)
        opt_bytes = 12.0 * n / (world if stage >= 1 else 1)
        grad_bytes = 4.0 * n / (world if stage >= 2 else 1)
        return p_bytes + opt_bytes + grad_bytes

    def feasible(self, stage: int, micro_batch: int, world: int) -> bool:
        """Memory prefilter. Models optimizer/param state exactly; the
        activation term needs ``activation_bytes_per_sample`` (caller-
        provided — the tuner cannot derive it from an opaque model)."""
        if self.hbm_bytes is None:
            return True
        need = self.estimate_state_bytes(stage, world)
        if self.activation_bytes_per_sample is not None:
            need += micro_batch * self.activation_bytes_per_sample
        return need < self.hbm_bytes

    # -------------------------------------------------------------- #
    def search_space(self) -> List[Dict[str, Any]]:
        return [{"zero_stage": s, "micro_batch": m}
                for s, m in itertools.product(self.zero_stages,
                                              self.micro_batch_sizes)]

    def candidate_features(self, cand: Dict[str, Any]):
        """Surrogate features for the model-based tuner: micro-batch
        terms, ZeRO stage, the memory model's state bytes, and (when
        the roofline peaks are known) a flops-derived throughput
        prediction — the per-module flops profiler's totals feed this
        through ``flops_per_sample``."""
        world = self._world()
        mb = float(cand["micro_batch"])
        feats = [mb, np.log2(mb), float(cand["zero_stage"]),
                 self.estimate_state_bytes(cand["zero_stage"], world)
                 / 1e9]
        if self.peak_flops and self.flops_per_sample:
            # predicted compute time per step (ms): grows with the micro
            # batch — the roofline signal the surrogate regresses against
            feats.append(self.flops_per_sample * mb / self.peak_flops
                         * 1e3)
        return feats

    def make_tuner(self):
        from deepspeed_tpu.autotuning.tuner import make_tuner

        return make_tuner(self.tuner_type, self.search_space(), self.rng,
                          features_fn=self.candidate_features)

    def _world(self) -> int:
        from deepspeed_tpu.parallel import groups

        return groups.get_topology().axis_size("dp")

    def _exp_config(self, cand: Dict[str, Any]) -> Dict[str, Any]:
        cfg = json.loads(json.dumps(self.base_config))  # deep copy
        cfg["train_micro_batch_size_per_gpu"] = cand["micro_batch"]
        cfg.pop("train_batch_size", None)
        zo = cfg.setdefault("zero_optimization", {})
        zo["stage"] = cand["zero_stage"]
        return cfg

    def _run_experiment(self, exp: Experiment) -> None:
        import jax

        import deepspeed_tpu
        from deepspeed_tpu.parallel import groups

        try:
            run_config = exp.config
            if self.fast:
                # fast mode inspects the micro program's cost analysis, so
                # keep micro/apply split FOR THE TRIAL ONLY — the recorded
                # / returned config must not carry the override
                run_config = {**exp.config, "fuse_optimizer_step": False}
            engine, _, _, _ = deepspeed_tpu.initialize(
                model=self.model, config=run_config,
                topology=groups.get_topology())
            args = self.sample_batch_fn(
                run_config["train_micro_batch_size_per_gpu"] *
                engine.dp_world_size)
            if self.fast:
                # compiler cost model: roofline step-time estimate
                # max(flops/peak_flops, bytes/peak_bw), scored as
                # samples/sec so bigger micro-batches only win when the
                # estimated time grows sublinearly
                engine.forward(*args)
                engine.backward(engine._last_loss)
                engine.step()
                lowered = engine._jit_micro.lower(*engine._micro_in_shapes)
                ca = lowered.compile().cost_analysis() or {}
                flops = float(ca.get("flops", 0.0))
                byts = float(ca.get("bytes accessed", 0.0))
                if flops <= 0 and byts <= 0:
                    raise RuntimeError("no cost analysis available")
                secs = max(flops / self.peak_flops, byts / self.peak_bw,
                           1e-12)
                exp.metric_val = engine.config.train_batch_size / secs
                return
            # measured throughput: warmup + timed steps
            for _ in range(1):
                loss = engine(*args)
                engine.backward(loss)
                engine.step()
            jax.block_until_ready(loss)
            # perf_counter, not time.time: the wall clock is not
            # monotonic (NTP steps corrupt a trial); block_until_ready
            # below waits for the final step's result so the bracket
            # measures compute, not dispatch (dslint timing-no-block)
            t0 = time.perf_counter()
            for _ in range(self.steps_per_trial):
                loss = engine(*args)
                engine.backward(loss)
                engine.step()
            jax.block_until_ready(loss)
            dt = (time.perf_counter() - t0) / self.steps_per_trial
            exp.metric_val = engine.config.train_batch_size / dt
        except Exception as e:  # noqa: BLE001 — OOM/compile failure prunes
            exp.error = f"{type(e).__name__}: {e}"
            logger.warning(f"autotuning experiment {exp.name} failed: "
                           f"{exp.error[:200]}")

    def _run_experiment_isolated(self, exp: Experiment) -> None:
        """Run one experiment in its OWN process so a hard crash / native
        OOM abort / hang cannot take down the tuning loop. Spawn (not
        fork): the parent's initialised XLA backend holds thread-pool
        locks a forked child would deadlock on. The child re-creates the
        parent's mesh; its platform is pinned to the parent's (a CPU-mesh
        parent must not have the child grab a TPU via ambient env)."""
        import multiprocessing as mp

        import cloudpickle
        import jax

        from deepspeed_tpu.parallel import groups

        import copy

        ctx = mp.get_context("spawn")
        recv, send = ctx.Pipe(duplex=False)
        lean = copy.copy(self)        # don't ship the experiment history
        lean.records = []
        payload = cloudpickle.dumps({
            "tuner": lean,
            "exp": exp,
            "mesh_dims": groups.get_topology().dims.as_dict(),
        })
        p = ctx.Process(
            target=_isolated_worker,
            args=(payload, len(jax.devices()),
                  jax.devices()[0].platform, send))
        p.start()
        send.close()
        metric = err = None
        if recv.poll(self.trial_timeout):
            try:
                metric, err = recv.recv()
            except EOFError:  # child died before sending
                pass
        else:
            err = f"trial timed out after {self.trial_timeout:.0f}s"
        p.join(5)
        if p.is_alive():
            p.terminate()
            p.join()
        if metric is None and err is None:
            err = f"experiment process died (exit code {p.exitcode})"
        exp.metric_val = metric
        exp.error = err
        if err and metric is None:
            # log ALL failures from the parent (hard ones — died/timeout —
            # and soft ones the child reported), so isolated-mode records
            # match in-process mode
            logger.warning(
                f"autotuning experiment {exp.name} failed: {err[:200]}")

    # -------------------------------------------------------------- #
    def tune(self) -> Dict[str, Any]:
        """Run the search; returns the best full DS config (reference
        ``tune:404`` — best exp written to results_dir)."""
        from deepspeed_tpu.parallel import groups
        from deepspeed_tpu.utils.platform import on_tpu

        if self.isolate and on_tpu():
            raise RuntimeError(
                "Autotuner(isolate=True) cannot run on a TPU: this process "
                "has initialised the backend and holds the chip, so a "
                "spawned experiment could never acquire it (one process "
                "per chip). Tune with isolate=False here, or isolate on a "
                "CPU mesh.")
        os.makedirs(self.results_dir, exist_ok=True)
        # Pin the user's topology: every experiment must run on the
        # production mesh, not a freshly-defaulted pure-DP one.
        topo = groups.get_topology()
        world = self._world()
        best: Optional[Experiment] = None
        tuner = self.make_tuner()
        trials = 0
        while trials < self.max_trials:
            cand = tuner.next()
            if cand is None:
                break
            name = f"z{cand['zero_stage']}_mbs{cand['micro_batch']}"
            if not self.feasible(cand["zero_stage"], cand["micro_batch"],
                                 world):
                logger.info(f"autotuning: {name} infeasible by memory "
                            f"model, skipped")
                tuner.update(cand, None)   # steer the surrogate away
                continue
            trials += 1
            exp = Experiment(name, self._exp_config(cand))
            groups.set_topology(topo)
            if self.isolate:
                self._run_experiment_isolated(exp)
            else:
                self._run_experiment(exp)
            tuner.update(cand, exp.metric_val)
            self.records.append(exp)
            with open(os.path.join(self.results_dir, f"{name}.json"),
                      "w") as f:
                json.dump(exp.record(), f, indent=2)
            if exp.metric_val is not None and \
                    (best is None or exp.metric_val > best.metric_val):
                best = exp
            logger.info(f"autotuning: {name} -> {exp.metric_val}")
        if best is None:
            raise RuntimeError("autotuning: every experiment failed")
        result = {"best_name": best.name, "best_metric_val": best.metric_val,
                  "metric": self.metric_name, "ds_config": best.config}
        with open(os.path.join(self.results_dir, "best.json"), "w") as f:
            json.dump(result, f, indent=2)
        logger.info(f"autotuning: best = {best.name} "
                    f"({self.metric_name}={best.metric_val:.1f})")
        return best.config
