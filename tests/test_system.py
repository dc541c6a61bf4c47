"""End-to-end behaviour: real training runs on CPU with the full
substrate (pipeline -> model -> policy -> optimizer -> checkpoint),
plus serving."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.ckpt import restore_checkpoint, save_checkpoint
from repro.configs import get_config
from repro.configs.shapes import TRAIN_4K
from repro.data.pipeline import PrefetchLoader, SyntheticLMDataset
from repro.models import transformer as T
from repro.optim.sgd import adamw


@pytest.fixture(scope="module")
def tiny_cfg():
    return get_config("qwen1.5-4b").reduced(num_layers=2, d_model=64,
                                            num_heads=2, d_ff=128,
                                            vocab_size=128)


def test_training_reduces_loss(tiny_cfg):
    cfg = tiny_cfg
    key = jax.random.PRNGKey(0)
    params = T.init_lm(cfg, key)
    opt = adamw(3e-3)
    state = opt.init(params)
    loader = PrefetchLoader(SyntheticLMDataset(cfg.vocab_size, 16, 8, seed=3),
                            depth=2)

    @jax.jit
    def step(params, state, tokens, labels):
        (l, m), g = jax.value_and_grad(
            lambda p: T.loss_fn(cfg, p, tokens, labels), has_aux=True)(params)
        params, state = opt.update(g, state, params)
        return params, state, l

    losses = []
    for i, batch in zip(range(30), loader):
        params, state, l = step(params, state,
                                jnp.asarray(batch["tokens"]),
                                jnp.asarray(batch["labels"]))
        losses.append(float(l))
    loader.close()
    assert np.isfinite(losses).all()
    assert np.mean(losses[-5:]) < np.mean(losses[:5])


def test_train_launcher_end_to_end(tmp_path):
    from repro.launch.train import build_argparser, run
    summary_path = tmp_path / "s.json"
    ckpt = tmp_path / "ck.npz"
    args = build_argparser().parse_args([
        "--arch", "gemma3-1b", "--steps", "6", "--batch", "4",
        "--seq", "32", "--policy", "single",
        "--checkpoint", str(ckpt), "--summary-json", str(summary_path)])
    summary = run(args)
    assert summary["steps"] == 6
    assert np.isfinite(summary["loss_last"])
    assert ckpt.exists() and summary_path.exists()


def test_serve_launcher_end_to_end():
    from repro.launch.serve import main
    summary = main(["--arch", "rwkv6-1.6b", "--batch", "2",
                    "--prompt-len", "8", "--gen", "8"])
    assert summary["generated"] == 8
    assert summary["decode_tok_per_s"] > 0


def test_checkpoint_resume_bitwise(tiny_cfg, tmp_path):
    cfg = tiny_cfg
    key = jax.random.PRNGKey(1)
    params = T.init_lm(cfg, key)
    opt = adamw(1e-3)
    state = opt.init(params)
    tokens = jax.random.randint(key, (4, 16), 0, cfg.vocab_size)
    labels = jax.random.randint(key, (4, 16), 0, cfg.vocab_size)

    @jax.jit
    def step(params, state):
        g = jax.grad(lambda p: T.loss_fn(cfg, p, tokens, labels)[0])(params)
        return opt.update(g, state, params)

    for _ in range(3):
        params, state = step(params, state)
    save_checkpoint(tmp_path / "ck.npz", params, state, step=3)
    cont_params, cont_state = params, state
    for _ in range(2):
        cont_params, cont_state = step(cont_params, cont_state)

    r_params, r_state, meta = restore_checkpoint(tmp_path / "ck.npz",
                                                 params, state)
    assert meta["step"] == 3
    for _ in range(2):
        r_params, r_state = step(r_params, r_state)
    for a, b in zip(jax.tree_util.tree_leaves(cont_params),
                    jax.tree_util.tree_leaves(r_params)):
        assert bool(jnp.all(a == b))


def test_dryrun_machinery_on_cpu_mesh():
    """The dry-run path (specs -> shardings -> lower -> compile ->
    analyses) on a 1x1 CPU mesh with a reduced config — the exact code
    path of the 512-device run."""
    from repro.launch import steps as S
    from repro.launch.mesh import make_cpu_mesh
    from repro.models import sharding as shd
    from repro.optim.sgd import sgd

    cfg = get_config("gemma3-1b").reduced(num_layers=2)
    mesh = make_cpu_mesh(1, 1)
    sc = shd.ShardingConfig(mesh_axes=mesh.axis_names, mode="fsdp")
    shd.set_sharding(sc)
    shd.set_mesh_sizes({"data": 1, "model": 1})
    try:
        pshape = S.params_shape(cfg)
        pspecs = shd.named_shardings(pshape, sc, mesh)
        opt = sgd(1e-2, momentum=0.9)
        oshape = jax.eval_shape(opt.init, pshape)
        ospecs = shd.named_shardings(oshape, sc, mesh)
        shape = dataclasses.replace(TRAIN_4K, seq_len=32, global_batch=4)
        specs = S.input_specs(cfg, shape)
        step = S.make_train_step(cfg, opt, remat=True)
        with jax.sharding.set_mesh(mesh):
            lowered = jax.jit(step, in_shardings=(pspecs, ospecs, None)) \
                .lower(pshape, oshape, specs)
            compiled = lowered.compile()
        assert compiled.cost_analysis() is not None
    finally:
        shd.set_sharding(None)
        shd.set_mesh_sizes(None)
