"""Sharding rules + a miniature end-to-end dry-run (8 fake devices, subprocess
so the XLA device-count flag can't leak into this test process)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest
from jax.sharding import PartitionSpec as P

from repro.config import MeshConfig
from repro.configs import get_config
from repro.models.module import ParamSpec, partition_specs
from repro.sharding.rules import make_rules, logical_spec


def test_rules_divisibility():
    """Axes are only assigned when the dim divides the mesh axis size."""
    mesh = MeshConfig()                      # data=16, model=16
    qwen3 = get_config("qwen3-14b")          # 40 heads -> not divisible by 16
    r = make_rules(qwen3, mesh)
    assert r["heads"] is None
    assert r["ffn"] == "model"               # 17408 % 16 == 0
    assert r["vocab"] == "model"
    granite = get_config("granite-34b")      # 48 heads, kv=1
    r2 = make_rules(granite, mesh)
    assert r2["heads"] == "model"
    assert r2["kv_heads"] is None            # 1 % 16 != 0
    mix = get_config("mixtral-8x22b")        # 8 experts -> no EP over data=16
    r3 = make_rules(mix, mesh)
    assert r3["experts"] is None
    moon = get_config("moonshot-v1-16b-a3b") # 64 experts -> EP over data
    r4 = make_rules(moon, mesh)
    assert r4["experts"] == "data"


def test_partition_specs_dedupe():
    """A mesh axis may appear at most once per spec."""
    rules = {"experts": "data", "embed": "data", "ffn": "model"}
    spec = {"w": ParamSpec((4, 8, 16), ("experts", "embed", "ffn"))}
    out = partition_specs(spec, rules)
    assert out["w"] == P("data", None, "model")


def test_logical_spec_multi_axis():
    rules = {"batch": ("pod", "data"), "seq": None, "vocab": "model"}
    assert logical_spec(("batch", "seq", "vocab"), rules) == P(("pod", "data"), None, "model")


@pytest.mark.slow
def test_mini_dryrun_subprocess():
    """A reduced arch lowers + compiles on a small fake mesh with the same
    machinery the production dry-run uses."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, json
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.config import MeshConfig, TrainConfig, InputShape
        from repro.configs import get_reduced_config
        from repro.models.api import (build_model, input_specs, input_shardings,
                                      make_train_step)
        from repro.models.module import partition_specs
        from repro.sharding.rules import make_rules, activation_sharding

        cfg = get_reduced_config("llama3.2-1b")
        mesh_cfg = MeshConfig(data=4, model=2)
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(4, 2)
        rules = make_rules(cfg, mesh_cfg, kind="train")
        api = build_model(cfg)
        shape = InputShape("mini", 64, 8, "train")
        ns = lambda p: NamedSharding(mesh, p)
        pspecs = partition_specs(api.specs, rules)
        p_shard = jax.tree_util.tree_map(ns, pspecs, is_leaf=lambda x: isinstance(x, P))
        b_specs = input_shardings(cfg, shape, mesh_cfg, rules)
        b_shard = jax.tree_util.tree_map(ns, b_specs, is_leaf=lambda x: isinstance(x, P))
        step, opt = make_train_step(api, TrainConfig())
        params_abs = api.abstract()
        opt_abs = jax.eval_shape(opt.init, params_abs)
        mv = p_shard
        o_shard = {"m": mv, "v": mv, "count": ns(P())}
        with mesh, activation_sharding(mesh, rules):
            lowered = jax.jit(step, in_shardings=(p_shard, o_shard, b_shard),
                              out_shardings=(p_shard, o_shard, None)).lower(
                params_abs, opt_abs, input_specs(cfg, shape))
            compiled = lowered.compile()
        cost = compiled.cost_analysis()
        print(json.dumps({"flops": cost.get("flops", 0.0),
                          "ok": True}))
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src"))
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["ok"] and out["flops"] > 0


def test_roofline_collective_parser():
    from repro.launch.roofline import collective_bytes
    hlo = """
    ENTRY %main {
      %ag = f32[16,1024]{1,0} all-gather(f32[2,1024] %x), dimensions={0}
      %ar = (bf16[8,128]{1,0}, bf16[8,128]{1,0}) all-reduce(...)
      %dot = f32[8,8] dot(...)
      %a2a = f32[4,256]{1,0} all-to-all(...)
    }
    """
    out = collective_bytes(hlo)
    assert out["all-gather"] == 16 * 1024 * 4      # result bytes only
    assert out["all-reduce"] == 2 * 8 * 128 * 2
    assert out["all-to-all"] == 4 * 256 * 4
    assert out["counts"]["all-gather"] == 1


def test_roofline_terms():
    from repro.launch.roofline import roofline_terms
    t = roofline_terms(197e12, 819e9, 50e9)
    assert t["compute_s"] == pytest.approx(1.0)
    assert t["memory_s"] == pytest.approx(1.0)
    assert t["collective_s"] == pytest.approx(1.0)
