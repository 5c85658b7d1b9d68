"""Launch layer: the one mesh constructor, the in-process serve entry point,
and chip_smoke.py's refusal to run anywhere but on a TPU."""

import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import jax
from jax.sharding import AxisType

from repro.launch import serve
from repro.launch.mesh import make_mesh, make_production_mesh
from repro.serving import kv_cache as kvc
from repro.serving import sharded as shd

jax.config.update("jax_platform_name", "cpu")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestMesh:
    def test_states_auto_axes(self):
        mesh = make_mesh(1, 1)
        assert mesh.axis_names == ("data", "model")
        assert tuple(mesh.axis_types) == (AxisType.Auto, AxisType.Auto)

    def test_too_many_devices_names_the_flag(self):
        with pytest.raises(ValueError, match="device_count=256"):
            make_production_mesh()

    def test_serving_rules_use_it(self):
        mesh = shd.rules_for(1, 1).mesh
        assert tuple(mesh.axis_types) == (AxisType.Auto, AxisType.Auto)

    def test_cache_scatter_under_the_launcher_mesh(self):
        """The launcher's cache reset is a scatter into a mesh-placed
        cache; with Explicit axes it raised ShardingTypeError."""
        from repro.configs import get_config

        cfg = get_config("phi4-mini-3.8b", smoke=True)
        rules = shd.rules_for(1, 1)
        layout = kvc.layout_for(cfg, 2, 16, kv_format="int8")
        cache = shd.shard_cache(kvc.init_cache_arrays(cfg, layout), cfg,
                                layout, rules)
        with rules.mesh:
            out = jax.jit(lambda c: kvc.reset_slot(c, layout, 1))(cache)
        assert np.all(np.asarray(out["pos"]) == 0)


class TestServeRun:
    def test_in_process_run_serves_every_request(self, capsys):
        args = serve.build_parser().parse_args(
            ["--smoke", "--requests", "2", "--slots", "2", "--max-new", "3",
             "--max-seq", "64"])
        steps = []
        stats, sched = serve.run(args, record_logits=True,
                                 on_step=lambda s: steps.append(s.step_count))
        assert stats["finished_requests"] == 2
        assert steps and steps == sorted(steps)
        for r in sched.finished:
            assert len(r.logit_rows) == len(r.generated) == r.max_new_tokens
        assert f"on {serve.device_label()}" in capsys.readouterr().out


class TestChipSmokeRefuses:
    def _run(self, cwd, env_extra):
        env = dict(os.environ, **env_extra)
        env.pop("PYTHONPATH", None)
        return subprocess.run(
            [sys.executable, "chip_smoke.py"], cwd=cwd, env=env,
            capture_output=True, text=True, timeout=300,
        )

    def test_without_a_tpu(self):
        out = self._run(REPO, {"JAX_PLATFORMS": "cpu"})
        assert out.returncode != 0
        assert "platform 'cpu'" in out.stderr
        assert '"ok"' not in out.stdout

    def test_alone_in_a_directory(self, tmp_path):
        shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
        out = self._run(str(tmp_path), {"JAX_PLATFORMS": "cpu"})
        assert out.returncode != 0
        assert '"ok"' not in out.stdout
