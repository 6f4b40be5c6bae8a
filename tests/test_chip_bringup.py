"""Who holds the chip, checked on the CPU mesh.

On a TPU host a chip belongs to the first process that initialises a jax
backend, so everything here is about *which* process that may be: counting
chips without opening them, no background thread bringing a backend up,
the head pinning every worker without a "TPU" claim to the CPU, the tune
trial actor holding the claim, a trainer refusing CPU devices it did not
ask for, one fixed compile-cache directory — and `chip_smoke.py`'s legs
run tiny, end to end. (Sorts before test_multi_node.py on purpose: tier-1
never reaches files after it, ROADMAP D0.)
"""

import os
import subprocess
import sys
import textwrap

import pytest

import ray_tpu

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _python(code: str, cwd: str = REPO, **env_changes) -> str:
    """Run `code` in a fresh interpreter (jax not imported, nothing
    inherited from pytest's process but the environment); a value of
    None removes the variable. Returns stdout."""
    env = dict(os.environ, PYTHONPATH=REPO)
    for k, v in env_changes.items():
        if v is None:
            env.pop(k, None)
        else:
            env[k] = v
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return proc.stdout


class TestCountingDoesNotOpen:
    def test_detect_tpus_never_touches_jax(self):
        out = _python("""
            import sys
            from ray_tpu._private.node import detect_tpus
            n = detect_tpus()
            assert "jax" not in sys.modules, "counting chips imported jax"
            print(n)
        """)
        assert float(out) == 0.0  # no device files in the sandbox

    def test_detect_tpus_counts_device_files(self, monkeypatch):
        from ray_tpu._private import node
        fake = {"/dev/accel[0-9]*": [],
                "/dev/vfio/[0-9]*": ["/dev/vfio/0", "/dev/vfio/1",
                                     "/dev/vfio/2", "/dev/vfio/3"]}
        monkeypatch.setattr(node.glob, "glob", fake.__getitem__)
        assert node.detect_tpus() == 4.0

    def test_no_background_thread_initialises_a_backend(self):
        # The runtime's metrics push loop and the profiling plane both
        # run in this driver; with jax imported but unused, neither may
        # bring a backend up (three push intervals pass).
        _python("""
            import time
            import ray_tpu
            ray_tpu.init(num_cpus=1)
            import jax
            from jax._src import xla_bridge
            from ray_tpu._private import profiling
            time.sleep(0.7)
            assert profiling.owns_device() is False
            assert profiling.device_memory_stats() == []
            assert not xla_bridge.backends_are_initialized()
            ray_tpu.shutdown()
        """, RAY_TPU_METRICS_INTERVAL_S="0.2")


class TestOneOwnerPerChip:
    @pytest.fixture
    def unpinned_driver(self, monkeypatch):
        """A runtime whose driver did not say `cpu` (as on a TPU host),
        with one chip to hand out."""
        monkeypatch.delenv("JAX_PLATFORMS")
        ray_tpu.init(num_cpus=4, num_tpus=1)
        yield
        ray_tpu.shutdown()

    def test_spawn_rule(self, unpinned_driver):
        @ray_tpu.remote
        def task_pin():
            return os.environ.get("JAX_PLATFORMS")

        @ray_tpu.remote
        class Pin:
            def get(self):
                return os.environ.get("JAX_PLATFORMS")

        plain = Pin.remote()
        owner = Pin.options(num_tpus=1).remote()
        said = Pin.options(env_vars={"JAX_PLATFORMS": "cpu"},
                           num_tpus=0).remote()
        assert ray_tpu.get(task_pin.remote()) == "cpu"     # pool worker
        assert ray_tpu.get(plain.get.remote()) == "cpu"    # no claim
        assert ray_tpu.get(owner.get.remote()) is None     # the owner
        assert ray_tpu.get(said.get.remote()) == "cpu"

    def test_plain_task_cannot_claim_a_chip(self, unpinned_driver):
        def f():
            return 1

        with pytest.raises(TypeError, match="num_tpus"):
            ray_tpu.remote(num_tpus=1)(f)
        with pytest.raises(ValueError, match="claims a TPU"):
            ray_tpu.remote(resources={"TPU": 1})(f).remote()

    def test_trial_actor_is_created_with_the_claim(self, tmp_path,
                                                   monkeypatch):
        """What `default_resource_request` computed is what the trial
        actor claims, so the head spawns that worker — and no other —
        with the device."""
        from ray_tpu.tune import Trainable, trial_executor
        from ray_tpu.tune.trial import Trial

        class Owner(Trainable):
            @classmethod
            def default_resource_request(cls, config):
                return {"CPU": 3, "TPU": config["tpus"]}

        claims = []

        class Recorder:
            def options(self, **claim):
                claims.append(claim)
                return self

            def remote(self, **kwargs):
                raise RuntimeError("recorded; no actor needed")

        monkeypatch.setattr(trial_executor.ray_tpu, "remote",
                            lambda cls: Recorder())
        for tpus in (4, 0):
            trial = Trial(Owner, config={"tpus": tpus},
                          local_dir=str(tmp_path))
            assert not trial_executor.RayTrialExecutor().start_trial(trial)
        assert claims == [{"num_cpus": 1, "num_tpus": 4},
                          {"num_cpus": 1, "num_tpus": None}]


class TestNoSilentCpu:
    def _mesh_for(self, n):
        from ray_tpu.rllib.agents.impala.impala import IMPALATrainer
        t = object.__new__(IMPALATrainer)
        t.config = {"num_tpus_for_learner": n}
        t._make_mesh()
        return t

    def test_cpu_devices_need_cpu_said_out_loud(self):
        import jax
        assert jax.config.jax_platforms == "cpu"  # conftest says it
        t = self._mesh_for(2)
        assert t._device == {"platform": "cpu", "kind": "cpu", "count": 2}
        jax.config.update("jax_platforms", None)
        try:
            with pytest.raises(RuntimeError, match="TPU is missing"):
                self._mesh_for(1)
            self._mesh_for(0)  # no TPU asked for: the default device
        finally:
            jax.config.update("jax_platforms", "cpu")

    def test_bench_refuses_unknown_device(self):
        import bench
        with pytest.raises(KeyError, match="device_kind 'cpu'"):
            bench.chip_peak_flops()


class TestCompileCachePlacement:
    CODE = """
        import os, jax
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        from ray_tpu.parallel import mesh
        print(mesh.place_compile_cache())
        if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            mesh.make_mesh(1)
            jax.jit(lambda x: x * 2 + 1)(jax.numpy.ones(8)).block_until_ready()
    """

    def test_env_var_wins_and_files_land_there(self, tmp_path):
        from ray_tpu.parallel import mesh

        def in_tree():
            return os.path.exists(mesh.COMPILE_CACHE_DIR) \
                and sorted(os.listdir(mesh.COMPILE_CACHE_DIR))

        where = str(tmp_path / "cache")
        before = in_tree()
        out = _python(self.CODE, JAX_COMPILATION_CACHE_DIR=where)
        assert out.strip() == where
        assert os.listdir(where), "no cache entry written where asked"
        assert in_tree() == before

    def test_default_is_one_fixed_in_tree_path(self, tmp_path):
        """Same directory in every process, wherever it was started."""
        from ray_tpu.parallel import mesh
        assert mesh.COMPILE_CACHE_DIR == os.path.join(REPO, ".jax_cache")
        out = _python(self.CODE, cwd=str(tmp_path),
                      JAX_COMPILATION_CACHE_DIR=None)
        assert out.strip() == mesh.COMPILE_CACHE_DIR

    def test_in_tree_cache_is_git_ignored(self):
        with open(os.path.join(REPO, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()

    def test_one_setter(self):
        hits = subprocess.run(
            ["grep", "-rlE", "--include=*.py",
             r"(update|set_cache_dir)\(.*compilation_cache_dir|"
             r"JAX_COMPILATION_CACHE_DIR.*=",
             "ray_tpu", "bench.py", "chip_smoke.py", "__graft_entry__.py"],
            cwd=REPO, capture_output=True, text=True).stdout.split()
        assert hits == ["ray_tpu/parallel/mesh.py"]


class TestChipSmokeLegs:
    """`chip_smoke.py`'s leg functions at toy sizes on the CPU mesh. The
    script itself has no switch that lets it pass without a chip."""

    @pytest.fixture(autouse=True)
    def _importable(self, monkeypatch):
        monkeypatch.syspath_prepend(REPO)

    def test_script_refuses_a_host_without_a_chip(self):
        proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                              capture_output=True, text=True, timeout=60)
        assert proc.returncode != 0
        assert proc.stdout == "" and "no TPU" in proc.stderr

    def test_remote_workers_leg_through_the_cli(self, tmp_path,
                                                monkeypatch):
        """Driver + trial actor + two rollout workers through `rllib
        train`: the trial is placed because the head was given the TPUs
        (finding 1's deadlock otherwise), trains on the devices it was
        given, and reports them in its results."""
        import chip_smoke
        from ray_tpu.cluster_utils import Cluster
        cluster = Cluster(head_resources={"CPU": 4, "TPU": 2})
        # The CLI driver attaches to this head instead of booting its
        # own, whose chip count on this host would be zero.
        monkeypatch.setenv("RAY_TPU_ADDRESS", cluster.head_addr)
        try:
            # Toy env and sizes: what is under test is who runs where,
            # and the Nature-CNN costs ~20 s of CPU compilation.
            obs = chip_smoke.remote_workers_cli_leg(
                2, "cpu", str(tmp_path), num_workers=2, iters=2,
                env="CartPole-v0", num_envs_per_worker=1,
                rollout_fragment_length=5, train_batch_size=10,
                min_iter_time_s=0)
        finally:
            cluster.shutdown()
        assert obs["device_count"] == 2 and obs["timesteps"] > 0
        assert os.path.exists(
            tmp_path / "remote_workers_cli" / "experiment.yaml")

    def test_sebulba_leg_in_process(self):
        import chip_smoke
        obs = chip_smoke.sebulba_leg(
            "sebulba_delta", "SpriteAtari-v0", "auto", 2, "cpu",
            n_actors=1, n_envs=8, frag=5, iters=2)
        assert obs["h2d_bytes"] > 0 and obs["device_count"] == 2

    def test_checks_reject_a_cpu_run(self):
        import chip_smoke
        result = {"training_iteration": 1, "timesteps_this_iter": 10,
                  "num_steps_trained": 10,
                  "info": {"learner": {"total_loss": 0.5}},
                  "device": {"platform": "cpu", "kind": "cpu", "count": 1,
                             "params_on": 1, "batch_on": 1,
                             "peak_bytes_in_use": None}}
        chip_smoke.check_results("leg", [result], 1, "cpu")
        with pytest.raises(AssertionError, match="expected 1 x tpu"):
            chip_smoke.check_results("leg", [result], 1, "tpu")
        nan = dict(result, info={"learner": {"total_loss": float("nan")}})
        with pytest.raises(AssertionError, match="not finite"):
            chip_smoke.check_results("leg", [nan], 1, "cpu")
        one = dict(result, device=dict(result["device"], count=4,
                                       params_on=4, batch_on=1))
        with pytest.raises(AssertionError, match="obs batch 1 of 4"):
            chip_smoke.check_results("leg", [one], 4, "cpu")
