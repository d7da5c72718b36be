"""Every architecture module in bench/reference/ keeps the harness's
contract at its own toy size (``TINY``) on the CPU, so that a module a
later configuration brings is checked with no new test code: its maker
lays the weights out as the program's ``model.init`` does, and the
engine's chunked prefill and paged decode agree with its ``logits``.  The
dense decoder's maker gives the arrays it gave before the harness took
architectures by name, and a configuration that names no module ends the
run."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import run
import tiny

MODULES = sorted(p.stem for p in (run.BENCH / "reference").glob("*.py")
                 if not p.stem.startswith("_"))
SEED = 20250917123
# the toy runs' own limit (tiny.spec): bf16 compute through the paged path
# against the float32 reference, at toy widths
LIMIT = 0.05
NEW_TOKENS = 12


def test_the_dense_decoder_is_among_them():
    assert "dense_decoder" in MODULES


@pytest.mark.parametrize("name", MODULES)
def test_maker_lays_out_the_programs_parameters(name):
    from repro.models import RuntimeConfig, build_model
    from repro.models import modules as M
    R = run.reference({"reference": name})
    cfg = run.model_config(R.TINY, R)
    model = build_model(cfg, RuntimeConfig(remat="none"))
    want = jax.eval_shape(lambda: M.unbox(model.init(jax.random.PRNGKey(0))))
    made = R.make(R.TINY["model"], SEED, layout=R.program_tree)
    assert jax.tree.structure(made) == jax.tree.structure(want)
    assert [(a.shape, a.dtype) for a in jax.tree.leaves(made)] == \
        [(a.shape, a.dtype) for a in jax.tree.leaves(want)]


def _served(name):
    """Two prompts, over a chunk and off a page boundary, through the
    engine's chunked prefill and paged decode; the spec and reference that
    judge them."""
    from repro.serve.scheduler import Request
    R = run.reference({"reference": name})
    spec = {"config": dict(R.TINY, reference=name),
            "traffic": {"engine": R.TINY["engine"],
                        "output": {"max": NEW_TOKENS}}}
    engine = run.build(spec, SEED, R)
    rng = np.random.default_rng(3)
    V = R.TINY["model"]["vocab"]
    reqs = [Request(rid=i, prompt=rng.integers(1, V, n).astype(np.int32),
                    max_new_tokens=NEW_TOKENS)
            for i, n in enumerate((37, 70))]
    for r in reqs:
        engine.submit(r)
    engine.run_until_drained()
    return spec, R, reqs


@pytest.mark.parametrize("name", MODULES)
def test_engine_prefill_and_decode_agree_with_the_reference(name):
    spec, R, reqs = _served(name)
    devs = jax.devices()[:1]
    gap, _, served = run.check(spec, SEED, reqs, R, devs, control=False)
    assert served == len(reqs) * NEW_TOKENS
    assert gap <= LIMIT
    # the comparison sees a token altered where it is produced
    V = R.TINY["model"]["vocab"]
    reqs[1].out[5] = (reqs[1].out[5] + 1) % V
    bad, _, _ = run.check(spec, SEED, reqs, R, devs, control=False)
    assert bad > LIMIT


def test_a_configuration_naming_no_module_ends_the_run():
    spec = tiny.spec()
    spec["config"]["reference"] = "no_such_architecture"
    with pytest.raises(SystemExit, match="no_such_architecture"):
        run.run(tiny.args(), spec)
    spec["config"]["reference"] = "../weights"
    with pytest.raises(SystemExit, match="no such module"):
        run.run(tiny.args(), spec)


# ------------------------------------------------------------- golden
# The dense decoder's maker as it stood before the harness took
# architectures by name: the engine's and the check's weights must stay
# these arrays, so that a cell's readings carry over.
def _seed_key(seed):
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _shapes(m):
    d, H, KV, hd, ff, V, L = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                              m["head_dim"], m["d_ff"], m["vocab"],
                              m["n_layers"])
    out = {
        "embed": ((V, d), "tied" if m["tie_embeddings"] else "embed"),
        "norm1": ((L, d), "norm"),
        "wq": ((L, d, H * hd), "matrix"),
        "wk": ((L, d, KV * hd), "matrix"),
        "wv": ((L, d, KV * hd), "matrix"),
        "wo": ((L, H * hd, d), "matrix"),
        "norm2": ((L, d), "norm"),
        "w_gate": ((L, d, ff), "matrix"),
        "w_up": ((L, d, ff), "matrix"),
        "w_down": ((L, ff, d), "matrix"),
        "final_norm": ((d,), "norm"),
    }
    if m["qkv_bias"]:
        out.update({"bq": ((L, H * hd), "bias"), "bk": ((L, KV * hd), "bias"),
                    "bv": ((L, KV * hd), "bias")})
    if not m["tie_embeddings"]:
        out["lm_head"] = ((d, V), "matrix")
    return out


def _draw(key, shape, kind):
    x = jax.random.normal(key, shape, jnp.float32)
    if kind == "matrix":
        return x * (shape[-2] ** -0.5)
    if kind == "bias":
        return 0.1 * x
    if kind == "norm":
        return 1.0 + 0.1 * x
    if kind == "tied":
        return x * (shape[-1] ** -0.5)
    return x


def _golden_make(m, seed):
    spec = _shapes(m)

    def gen(key):
        return {name: _draw(jax.random.fold_in(key, i), shape, kind)
                for i, (name, (shape, kind)) in enumerate(spec.items())}
    return jax.jit(gen)(_seed_key(seed))


@pytest.mark.parametrize("tied", [False, True])
def test_dense_make_gives_the_golden_arrays(tied):
    R = run.reference({"reference": "dense_decoder"})
    m = dict(tiny.MODEL, tie_embeddings=tied, qkv_bias=not tied)
    want = _golden_make(m, SEED)
    flat = R.make(m, SEED)
    tree = R.make(m, SEED, layout=R.program_tree)
    assert sorted(flat) == sorted(want)
    for k in want:
        assert np.array_equal(np.asarray(flat[k]), np.asarray(want[k])), k
    assert all(np.array_equal(np.asarray(a), np.asarray(b)) for a, b in zip(
        jax.tree.leaves(tree), jax.tree.leaves(R.program_tree(want))))
