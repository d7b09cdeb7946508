"""The way back from the sorted rows (core._moe_dropless's ``moe.combine``):
the grouped product's rows are gathered back as the product left them, then
converted, masked by ``inv < n_live``, weighted by the router's [N, k] weights
and summed over k in one float32 expression. Held here on the CPU for every
kind of dropless layer the suite has: sigmoid with a bias and a shared expert
(tiny-joyai), softmax-top-k with neither (tiny-smallthinker), and the two
expert SHARES (tiny-granite, tiny-exaone: 4 of 8 / 16 experts held from the
4th on), against the layer written out a token at a time and against a
grouped product that returns NaN in every row that belongs to no group."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bee2bee_tpu.models import core
from bee2bee_tpu.models.config import get_config

MODELS = ["tiny-joyai", "tiny-smallthinker", "tiny-granite", "tiny-exaone"]


def _layer(cfg, seed=0):
    """One expert layer's parameters in float32, as core._init_params shapes
    them (the held experts' stacks, the router over EVERY expert)."""
    D, E, Eh, F = cfg.d_model, cfg.n_experts, cfg.experts_held, cfg.expert_ff
    key = iter(jax.random.split(jax.random.key(seed), 8))
    p = {"router": jax.random.normal(next(key), (D, E)),
         "w_gate": jax.random.normal(next(key), (Eh, D, F)) / np.sqrt(D),
         "w_up": jax.random.normal(next(key), (Eh, D, F)) / np.sqrt(D),
         "w_down": jax.random.normal(next(key), (Eh, F, D)) / np.sqrt(F)}
    if cfg.moe_router == "sigmoid" and cfg.moe_select_bias:
        p["router_bias"] = 0.1 * jax.random.normal(next(key), (E,))
    if cfg.n_shared_experts:
        Fs = cfg.shared_ff
        p["shared"] = {
            "w_gate": jax.random.normal(next(key), (D, Fs)) / np.sqrt(D),
            "w_up": jax.random.normal(next(key), (D, Fs)) / np.sqrt(D),
            "w_down": jax.random.normal(next(key), (Fs, D)) / np.sqrt(Fs)}
    return p


def _case(cfg):
    """(x [3, 6, D], live [3, 6] with a padded tail and a dead row)."""
    x = jax.random.normal(jax.random.key(21), (3, 6, cfg.d_model))
    live = jnp.ones((3, 6), bool).at[1, 4:].set(False).at[2].set(False)
    return x, live


def _token_at_a_time(x, p, cfg, live):
    """The layer with no sort, no group and no gather: every live token's
    chosen experts one after another, in the order the router gave them,
    weighted and summed in float32; an expert held elsewhere gives nothing;
    then the shared expert."""
    B, T, D = x.shape
    xf = np.asarray(x, np.float32).reshape(B * T, D)
    topi, w = (np.asarray(a) for a in core._moe_router(jnp.asarray(xf), p, cfg))
    out = np.zeros((B * T, D), np.float32)
    for n in np.flatnonzero(np.asarray(live).reshape(-1)):
        for j in range(cfg.n_experts_per_tok):
            e = int(topi[n, j]) - cfg.expert_first
            if not 0 <= e < cfg.experts_held:
                continue
            row = jnp.asarray(xf[n:n + 1])
            h = core._activate(row @ p["w_up"][e], row @ p["w_gate"][e], cfg)
            out[n] += np.float32(w[n, j]) * np.asarray(h @ p["w_down"][e])[0]
    if "shared" in p:
        out += np.asarray(core._mlp(jnp.asarray(xf), p["shared"], cfg))
    return out.reshape(B, T, D)


@pytest.mark.parametrize("model", MODELS)
def test_the_layer_equals_its_tokens_one_at_a_time(model):
    cfg = get_config(model)
    p = _layer(cfg)
    x, live = _case(cfg)
    got, stats = core._moe_dropless(x, p, cfg, live=live)
    want = _token_at_a_time(x, p, cfg, live)
    shared = (np.asarray(core._mlp(x, p["shared"], cfg)) if "shared" in p
              else np.zeros_like(want))
    # a dead position gets the shared expert alone (the caller drops the row)
    want = np.where(np.asarray(live)[..., None], want, shared)
    scale = np.abs(want).max()
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-6 * scale, rtol=0)
    assert got.dtype == x.dtype
    names = core.moe_stats_names(cfg)
    said = dict(zip(names, np.asarray(stats).tolist()))
    n_live = int(np.asarray(live).sum()) * cfg.n_experts_per_tok
    assert said["live"] + said.get("elsewhere", 0) == n_live
    if cfg.expert_share:
        assert 0 < said["elsewhere"] < n_live  # the case does hold both kinds


@pytest.mark.parametrize("model", MODELS)
def test_rows_of_no_group_may_hold_nan(model, monkeypatch):
    """A grouped product whose rows past ``sum(group_sizes)`` come back NaN
    (through all three products: the chip leaves there whatever the buffer
    held): the layer's output is finite and the same, with dead positions
    and, under a share, with assignments held elsewhere in the pad group."""
    cfg = get_config(model)
    p = _layer(cfg, seed=1)
    x, live = _case(cfg)
    clean, clean_stats = core._moe_dropless(x, p, cfg, live=live)
    real = core.grouped_matmul
    seen = []

    def poisoned(xs, w, sizes, *a, **kw):
        out = real(xs, w, sizes, *a, **kw)
        dead = jnp.arange(xs.shape[0]) >= jnp.sum(sizes)
        seen.append(dead)
        return jnp.where(dead[:, None], jnp.nan, out)

    monkeypatch.setattr(core, "grouped_matmul", poisoned)
    got, stats = core._moe_dropless(x, p, cfg, live=live)
    assert len(seen) == 3 and all(int(d.sum()) > 0 for d in seen)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(clean))
    np.testing.assert_array_equal(np.asarray(stats), np.asarray(clean_stats))
