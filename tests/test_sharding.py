"""Sharding rules + multi-device execution (subprocess with 8 host devices:
this process already initialized jax with 1 CPU device, so device-count tests
run in a child interpreter — same mechanism as the dry-run)."""
import json
import os
import pathlib
import subprocess
import sys
import textwrap

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ARCHS
from repro.models import Runtime, build_model, input_specs
from repro.sharding.rules import batch_pspecs, cache_pspecs, param_pspecs

REPO = pathlib.Path(__file__).resolve().parents[1]


class FakeMesh:
    axis_names = ("data", "model")

    class _Dev:
        shape = (16, 16)

    devices = _Dev()


def test_param_rules_cover_big_tensors():
    """Every parameter above 1M elements must be sharded on 'model' (nothing
    big silently replicated)."""
    import numpy as np

    for name in ("llama3-8b", "deepseek-v2-lite-16b", "llama4-maverick-400b-a17b"):
        cfg = ARCHS[name]
        model = build_model(cfg)
        shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        specs = param_pspecs(shapes, FakeMesh())
        flat_s, _ = jax.tree_util.tree_flatten_with_path(shapes)
        flat_p = jax.tree_util.tree_leaves(specs)
        # anything above 256 MB must be sharded; smaller leaves (MLA
        # LoRA-down ~54 MB, routers ~31 MB) are deliberately replicated to
        # avoid per-layer gathers (see sharding/rules.py)
        for (path, leaf), spec in zip(flat_s, flat_p):
            name = jax.tree_util.keystr(path)
            bytes_ = np.prod(leaf.shape) * leaf.dtype.itemsize
            if bytes_ > 256 * 2**20:
                assert any(ax == "model" for ax in spec if ax), (
                    f"{name} {leaf.shape} ({bytes_/2**20:.0f} MB) replicated"
                )


def test_param_rules_respect_divisibility():
    cfg = ARCHS["smollm-360m"]  # 15 heads, d=960: not all dims divide by 16
    model = build_model(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    specs = param_pspecs(shapes, FakeMesh())
    flat_s = jax.tree_util.tree_leaves(shapes)
    flat_p = jax.tree_util.tree_leaves(specs)
    for leaf, spec in zip(flat_s, flat_p):
        for dim, ax in enumerate(spec):
            if ax == "model":
                assert leaf.shape[dim] % 16 == 0


def test_batch_specs():
    cfg = ARCHS["tinyllama-1.1b"]
    specs = batch_pspecs(input_specs(cfg, 256, 128), FakeMesh())
    assert specs["tokens"] == P(("data",), None)
    # non-divisible batch replicates
    specs1 = batch_pspecs(input_specs(cfg, 1, 128), FakeMesh())
    assert specs1["tokens"] == P()


def test_cache_specs_cover_all_archs():
    rt = Runtime()
    for name, cfg in ARCHS.items():
        model = build_model(cfg)
        cache = jax.eval_shape(lambda m=model: m.init_cache(128, 256, rt))
        specs = cache_pspecs(cfg, cache, FakeMesh())
        flat_c = [x for x in jax.tree_util.tree_leaves(cache)]
        flat_s = jax.tree_util.tree_leaves(specs)
        assert len(flat_c) == len(flat_s)
        for leaf, spec in zip(flat_c, flat_s):
            for dim, ax in enumerate(spec):
                if ax is None:
                    continue
                axes = (ax,) if isinstance(ax, str) else tuple(ax)
                size = 1
                for a in axes:
                    size *= {"data": 16, "model": 16, "pod": 2}[a]
                assert leaf.shape[dim] % size == 0, (name, leaf.shape, spec)


MULTIDEV_SNIPPET = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    from repro.configs import ARCHS
    from repro.models import build_model, Runtime, lm_loss, make_input_batch
    from repro.sharding.rules import param_pspecs, batch_pspecs, to_shardings
    from repro.optim.optimizer import OptConfig, init_opt_state
    from repro.train.steps import make_train_step
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(model_axis=4)  # (data=2, model=4)
    cfg = ARCHS["tinyllama-1.1b"].reduced()
    model = build_model(cfg)
    rt = Runtime()
    params = model.init(jax.random.PRNGKey(0))
    opt = init_opt_state(params)
    batch = make_input_batch(cfg, 4, 32)
    with mesh:
        p_sh = to_shardings(param_pspecs(jax.eval_shape(lambda: params), mesh), mesh)
        b_sh = to_shardings(batch_pspecs(jax.eval_shape(lambda: batch), mesh), mesh)
        params = jax.device_put(params, p_sh)
        batch = jax.device_put(batch, b_sh)
        step = jax.jit(make_train_step(model, OptConfig(), rt))
        params2, opt2, metrics = step(params, opt, batch)
        loss1 = float(metrics["loss"])
        params3, opt3, metrics2 = step(params2, opt2, batch)
        loss2 = float(metrics2["loss"])
    print(json.dumps({"loss1": loss1, "loss2": loss2,
                      "n_dev": len(jax.devices())}))
    """
)


@pytest.mark.slow
def test_multidevice_train_step_runs():
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", MULTIDEV_SNIPPET],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["n_dev"] == 8
    assert res["loss2"] < res["loss1"]  # actually learns under pjit
