"""Model-semantics tests beyond smoke: equivariance, SWA, MoE math."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.configs.base import MoEConfig


def _rot_matrix(key):
    """Random rotation via QR."""
    A = jax.random.normal(key, (3, 3))
    Q, R = jnp.linalg.qr(A)
    return Q * jnp.sign(jnp.diag(R))[None, :]


@pytest.mark.parametrize("arch", ["mace", "egnn", "schnet"])
def test_geometric_invariance(arch):
    """Rotating + translating all positions must not change the (scalar)
    node embeddings — the equivariance contract of the geometric GNNs."""
    from repro.models.gnn import steps as gsteps
    from repro.models.gnn.common import batch_molecules
    cfg = get_smoke(arch)
    batch = batch_molecules(4, 8, 14, 4, seed=0)
    params = gsteps.init_params(cfg, jax.random.key(0))
    mod = gsteps.model_module(cfg)
    h0 = mod.node_embeddings(params, cfg, batch)
    R = _rot_matrix(jax.random.key(5))
    batch2 = dict(batch)
    batch2["positions"] = np.asarray(batch["positions"] @ np.asarray(R).T
                                     + 1.7)
    h1 = mod.node_embeddings(params, cfg, batch2)
    np.testing.assert_allclose(np.asarray(h0, np.float32),
                               np.asarray(h1, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_swa_masks_far_context():
    """With window w, tokens farther than w in the past cannot influence
    the output: perturb an early token, outputs beyond the window match."""
    from repro.models.transformer import model as M
    cfg = get_smoke("mixtral-8x22b")       # window 32
    params = M.init_params(cfg, jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (1, 96), 0, cfg.vocab)
    toks2 = toks.at[0, 0].set((toks[0, 0] + 1) % cfg.vocab)
    h1, _ = M.forward_hidden(params, cfg, toks)
    h2, _ = M.forward_hidden(params, cfg, toks2)
    # effective receptive field after L=2 layers = L*w = 64: beyond that,
    # position 0 cannot reach the output
    diff = np.abs(np.asarray(h1 - h2, np.float32)).max(axis=-1)[0]
    assert diff[80:].max() < 1e-3
    assert diff[:16].max() > 1e-3           # but it does change nearby


def test_moe_virtual_split_is_exact():
    """split-2 virtual experts must equal the unsplit computation when the
    params are tied accordingly."""
    from repro.models.transformer import model as M
    base = get_smoke("mixtral-8x22b")
    cfg1 = dataclasses.replace(
        base, moe=MoEConfig(n_experts=4, top_k=2, d_ff_expert=128,
                            virtual_split=1))
    cfg2 = dataclasses.replace(
        base, moe=MoEConfig(n_experts=4, top_k=2, d_ff_expert=128,
                            virtual_split=2))
    p1 = M.init_params(cfg1, jax.random.key(0))
    # build split params from p1: expert e -> (e*2, e*2+1) halves along f
    p2 = jax.tree.map(lambda x: x, p1)
    moe1 = p1["layers"]["moe"]
    L, E, d, f = moe1["w_up"].shape

    def split_up(w):      # (L, E, d, f) -> (L, 2E, d, f/2)
        return w.reshape(L, E, d, 2, f // 2).transpose(0, 1, 3, 2, 4) \
                .reshape(L, 2 * E, d, f // 2)

    def split_down(w):    # (L, E, f, d) -> (L, 2E, f/2, d)
        return w.reshape(L, E, 2, f // 2, d).reshape(L, 2 * E, f // 2, d)

    p2["layers"]["moe"] = dict(moe1)
    p2["layers"]["moe"]["w_up"] = split_up(moe1["w_up"])
    p2["layers"]["moe"]["w_gate"] = split_up(moe1["w_gate"])
    p2["layers"]["moe"]["w_down"] = split_down(moe1["w_down"])

    toks = jax.random.randint(jax.random.key(3), (2, 32), 0, base.vocab)
    h1, _ = M.forward_hidden(p1, cfg1, toks)
    h2, _ = M.forward_hidden(p2, cfg2, toks)
    np.testing.assert_allclose(np.asarray(h1, np.float32),
                               np.asarray(h2, np.float32), atol=3e-2)


def test_moe_pad_experts_never_selected():
    from repro.models.transformer import model as M
    cfg = get_smoke("qwen2-moe-a2.7b")   # 8 experts padded to 10
    params = M.init_params(cfg, jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (2, 32), 0, cfg.vocab)
    h, aux = M.forward_hidden(params, cfg, toks)
    assert bool(jnp.isfinite(h.astype(jnp.float32)).all())
    # dummy-expert weights receive zero gradient
    g = jax.grad(lambda p: M.lm_loss(p, cfg, toks,
                                     jnp.roll(toks, -1, 1)))(params)
    gu = np.asarray(g["layers"]["moe"]["w_up"])  # (L, E_eff, d, f)
    assert np.abs(gu[:, cfg.moe.n_experts:, :, :]).max() == 0.0


def test_lm_loss_decreases_with_training():
    """End-to-end: 30 steps on the smoke config actually learn."""
    from repro.data import synth_lm_batch
    from repro.models.transformer import model as M
    from repro.models.transformer.steps import make_train_step
    from repro.optim import AdamWConfig, adamw_init
    cfg = get_smoke("qwen1.5-0.5b")
    params = M.init_params(cfg, jax.random.key(0))
    opt = adamw_init(params)
    step = jax.jit(make_train_step(cfg, None,
                                   AdamWConfig(lr=3e-3, weight_decay=0.0),
                                   total_steps=30))
    losses = []
    for i in range(30):
        t, l = synth_lm_batch(cfg.vocab, 8, 64, seed=0, step=i)
        params, opt, m = step(params, opt, jnp.asarray(t), jnp.asarray(l))
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.5, (losses[0], losses[-1])


def test_embedding_bag_modes():
    from repro.models.recsys.embedding_bag import (embedding_bag,
                                                   ragged_embedding_bag)
    table = jnp.arange(12.0).reshape(4, 3)
    idx = jnp.array([[0, 1, -1], [2, -1, -1]])
    s = embedding_bag(table, idx, mode="sum")
    np.testing.assert_allclose(np.asarray(s[0]), np.asarray(table[0] +
                                                            table[1]))
    m = embedding_bag(table, idx, mode="mean")
    np.testing.assert_allclose(np.asarray(m[1]), np.asarray(table[2]))
    r = ragged_embedding_bag(table, jnp.array([0, 1, 2]),
                             jnp.array([0, 0, 1]), 2)
    np.testing.assert_allclose(np.asarray(r[0]),
                               np.asarray(table[0] + table[1]))
