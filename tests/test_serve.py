import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import transformer as T
from repro.launch.serve import ServeEngine, make_serve_step


@pytest.fixture(scope="module")
def engine():
    cfg = get_config("granite-3-2b").smoke_config()
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, ServeEngine(cfg, params, max_seq=64)


def test_generate_shapes_and_determinism(engine):
    cfg, eng = engine
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                              cfg.vocab_size)
    out1 = eng.generate({"tokens": toks}, n_new=8)
    out2 = eng.generate({"tokens": toks}, n_new=8)
    assert out1.shape == (2, 8)
    assert np.array_equal(np.asarray(out1), np.asarray(out2))
    assert int(jnp.max(out1)) < cfg.vocab_padded


def test_generate_matches_stepwise_forward(engine):
    """Greedy engine output == argmax over repeated full forwards."""
    cfg, eng = engine
    toks = jax.random.randint(jax.random.PRNGKey(2), (1, 12), 0,
                              cfg.vocab_size)
    out = np.asarray(eng.generate({"tokens": toks}, n_new=4))
    cur = np.asarray(toks)
    for i in range(4):
        x, _ = T.forward(eng.params, {"tokens": jnp.asarray(cur)}, cfg)
        logits = T.logits_from_hidden(eng.params, x[:, -1:, :], cfg)
        nxt = int(jnp.argmax(logits[0, -1]))
        assert nxt == out[0, i], f"step {i}: {nxt} vs {out[0, i]}"
        cur = np.concatenate([cur, [[nxt]]], axis=1)


def test_serve_step_moe_arch():
    cfg = get_config("qwen3-moe-30b-a3b").smoke_config()
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    caches = jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype), T.cache_defs(cfg, 2, 32))
    step = jax.jit(make_serve_step(cfg))
    tok = jnp.zeros((2, 1), jnp.int32)
    nxt, caches = step(params, tok, caches, jnp.asarray(0, jnp.int32))
    assert nxt.shape == (2, 1)
    nxt, _ = step(params, nxt, caches, jnp.asarray(1, jnp.int32))
    assert np.all(np.asarray(nxt) >= 0)
