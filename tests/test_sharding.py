"""Sharding layer: logical rules, param-spec pattern matching, and a
subprocess smoke of the real dry-run (which needs 512 host devices)."""
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config, get_smoke_config
from repro.launch.mesh import make_host_mesh
from repro.models.layers import tree_paths
from repro.sharding.api import (constrain, lm_decode_rules,
                                lm_long_decode_rules, lm_rules,
                                mesh_context)
from repro.sharding.params import (lm_param_rules, opt_state_specs,
                                   param_specs, spec_for_path)


def test_lm_param_rules_matching():
    rules = lm_param_rules("data")
    assert spec_for_path("moe_layers/attn/wq/w", 3, rules) == \
        P(None, "data", "model")
    assert spec_for_path("dense_layers/attn/wo/w", 3, rules) == \
        P(None, "model", "data")
    assert spec_for_path("moe_layers/moe/w1", 4, rules) == \
        P(None, "model", "data", None)
    assert spec_for_path("embed/table", 2, rules) == P("model", "data")
    assert spec_for_path("final_norm/scale", 1, rules) == P(None)
    assert spec_for_path("unknown/thing", 2, rules) == P()


def test_param_specs_cover_full_tree():
    from repro.models.transformer import init_transformer
    cfg = get_smoke_config("kimi-k2-1t-a32b")
    p = jax.eval_shape(lambda k: init_transformer(k, cfg),
                       jax.random.PRNGKey(0))
    specs = param_specs(p, lm_param_rules("data"))
    flat_p = dict(tree_paths(p))
    flat_s = dict(tree_paths(specs)) if False else None
    # same tree structure
    assert jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda x: 0, p)) == \
        jax.tree_util.tree_structure(
            jax.tree_util.tree_map(lambda s: 0, specs,
                                   is_leaf=lambda x: isinstance(x, P)))
    # every spec rank matches its leaf rank or is replicated
    def check(leaf, spec):
        assert isinstance(spec, P)
        assert len(spec) in (0, len(leaf.shape))
    jax.tree_util.tree_map(check, p, specs,
                           is_leaf=lambda x: hasattr(x, "shape"))


def test_opt_state_specs_adafactor_reduced_dims():
    from repro.models.transformer import init_transformer
    from repro.train.optimizer import make_optimizer
    cfg = get_smoke_config("kimi-k2-1t-a32b")
    p = jax.eval_shape(lambda k: init_transformer(k, cfg),
                       jax.random.PRNGKey(0))
    specs = param_specs(p, lm_param_rules("data"))
    opt = make_optimizer("adafactor", 1e-3)
    o = jax.eval_shape(opt.init, p)
    o_specs = opt_state_specs(o, specs, "adafactor")
    # moe w1 [L, E, d, f] -> spec (None, model, data, None);
    # vr drops last dim, vc drops second-to-last
    slot = o_specs["slots"]["moe_layers"]["moe"]["w1"]
    assert slot["vr"] == P(None, "model", "data")
    assert slot["vc"] == P(None, "model", None)


def test_rules_consistency():
    r = lm_rules("data", attn_shard="heads")
    assert r["heads"] == "model" and r["qseq"] is None
    r2 = lm_rules("data", attn_shard="sequence")
    assert r2["heads"] is None and r2["qseq"] == "model"
    rd = lm_decode_rules("data")
    assert rd["kvseq"] == "model"
    rl = lm_long_decode_rules("data")
    assert rl["kvseq"] == ("data", "model") and rl["batch"] is None


def test_constrain_noop_without_context():
    x = jnp.ones((4, 4))
    y = constrain(x, "batch", None)
    assert y is x


def test_constrain_applies_in_context():
    mesh = make_host_mesh()
    with mesh_context(mesh, {"batch": "data"}):
        y = jax.jit(lambda x: constrain(x, "batch", None))(jnp.ones((4, 4)))
    assert y.shape == (4, 4)


@pytest.mark.slow
def test_dryrun_subprocess_one_cell():
    """The real dry-run entry point, in a fresh process (512 host devs)."""
    cmd = [sys.executable, "-m", "repro.launch.dryrun",
           "--arch", "qwen3-0.6b", "--cell", "decode_32k"]
    r = subprocess.run(cmd, capture_output=True, text=True, timeout=900,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "HOME": "/root"}, cwd="/root/repo")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "1 ok, 0 failed" in r.stdout
