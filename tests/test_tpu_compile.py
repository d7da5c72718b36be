"""The served path's Pallas kernels compile for a TPU v5e, without the chip.

Each test lowers one kernel at qwen1.5-0.5b widths (d_model 1024, 16 heads
of 64, d_ff 2816, vocab 151936; the engine's 8 slots x 1024 tokens in pages
of 16) for one chip of a described ``v5e:2x2`` topology, at the kernel's
default config, and asserts that the compiled program holds a Mosaic kernel
(``tpu_custom_call``) rather than the interpreter's loop.  One more checks
that exact-mode engine steps compile with XLA's excess precision off, and
two that the engine's decode and chunk steps update the paged KV pools in
place: no copy, slice or write-back of a pool in the compiled program.

The topology is described inside a module fixture, never while a module is
imported: only one process at a time may load the TPU library, and every
test worker must collect the same tests.  Keep all such compiles in this one
file, so that one worker loads the library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops as K

D, HEADS, HD, FF, VOCAB = 1024, 16, 64, 2816, 151936
SLOTS, PAGE, BLOCKS, CHUNK = 8, 16, 1024 // 16, 32
POOL = SLOTS * BLOCKS + 1


@pytest.fixture(scope="module")
def topo(tmp_path_factory):
    from jax.experimental import topologies
    # the TPU library logs under /tmp unless given a directory
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", str(tmp_path_factory.mktemp("tpu_logs")))
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compile_for_tpu(one_chip, monkeypatch):
    """Compiles ``fn`` at the given shapes for one described v5e chip.

    The default backend reads as ``tpu`` so that ``pallas_interpret()``
    picks the compiled lowering; the persistent cache is off (a TPU entry
    written here could not be read back) and the trace caches are cleared
    on both sides, so no CPU trace leaks into the TPU lowering or back."""
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.clear_caches()

    def compile_(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
                for s, dt in shapes]
        return jax.jit(fn).lower(*args).compile().as_text()

    yield compile_
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", was)


bf16, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.mark.parametrize("N,K_", [(D, FF), (FF, D), (VOCAB, D)],
                         ids=["down", "up", "lm_head"])
def test_gemv_compiles(compile_for_tpu, N, K_):
    txt = compile_for_tpu(K.gemv, ((N, K_), bf16), ((K_,), bf16))
    assert "tpu_custom_call" in txt


def test_dotp_compiles(compile_for_tpu):
    txt = compile_for_tpu(K.dotp, ((1 << 24,), bf16), ((1 << 24,), bf16))
    assert "tpu_custom_call" in txt


def test_axpy_compiles(compile_for_tpu):
    txt = compile_for_tpu(lambda x, y: K.axpy(1.5, x, y),
                          ((1 << 24,), bf16), ((1 << 24,), bf16))
    assert "tpu_custom_call" in txt


def test_rmsnorm_compiles(compile_for_tpu):
    txt = compile_for_tpu(K.rmsnorm, ((SLOTS, D), bf16), ((D,), f32))
    assert "tpu_custom_call" in txt


def test_paged_decode_attention_compiles(compile_for_tpu):
    pool = ((POOL, PAGE, HEADS, HD), bf16)
    txt = compile_for_tpu(K.paged_decode_attention,
                          ((SLOTS, HEADS, HD), bf16), pool, pool,
                          ((SLOTS, BLOCKS), i32), ((SLOTS,), i32))
    assert "tpu_custom_call" in txt


def test_prefill_attention_paged_compiles(compile_for_tpu):
    pool = ((POOL, PAGE, HEADS, HD), bf16)
    txt = compile_for_tpu(K.prefill_attention_paged,
                          ((1, CHUNK, HEADS, HD), bf16), pool, pool,
                          ((1, BLOCKS), i32), ((1,), i32), ((1,), i32))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("mode,rounds", [("exact", True), ("overlap", False)])
def test_exact_mode_rounds_every_bf16_op(compile_for_tpu, one_chip, mode,
                                         rounds):
    """Exact-mode engine steps compile with XLA's excess precision off, so
    a bf16 chain rounds after every op wherever its fusion ends."""
    from repro.dist.tp import compiler_options

    def chain(x, w):
        h = x @ w
        return (jax.nn.silu(h) * h).astype(bf16) @ w.T

    args = [jax.ShapeDtypeStruct(s, bf16, sharding=one_chip)
            for s in ((SLOTS, D), (D, D))]
    txt = jax.jit(chain, compiler_options=compiler_options(mode)).lower(
        *args).compile().as_text()
    assert ("reduce-precision" in txt) == rounds


@pytest.mark.parametrize("step", ["decode", "chunk"])
def test_step_updates_kv_pools_in_place(compile_for_tpu, one_chip, step):
    """The whole qwen1.5-0.5b decode and chunk steps over the engine's paged
    pools hold no ``copy``, ``dynamic-slice`` or ``dynamic-update-slice``
    whose result has a pool's (P, page) dims, per layer or stacked; the
    donated pools alias the outputs; and the temporaries stay under the
    pools' size.  Parameters are bf16, so no converted weight copy sits in
    the temporaries and what remains is the step's own.  Fails the day
    XLA reinserts a pool copy."""
    import re

    from repro.configs import get_config
    from repro.models import RuntimeConfig, build_model
    from repro.models import modules as M
    from repro.serve.kvcache import PageSpec
    from repro.serve.step import make_chunk_step, make_serve_step

    model = build_model(get_config("qwen1.5-0.5b"),
                        RuntimeConfig(remat="none"))
    spec = PageSpec.for_engine(SLOTS, BLOCKS * PAGE, PAGE)

    def shapes(tree, dtype=None):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, dtype or a.dtype, sharding=one_chip), tree)

    params = shapes(jax.eval_shape(
        lambda: M.unbox(model.init(jax.random.PRNGKey(0)))), bf16)
    caches = shapes(jax.eval_shape(
        lambda: model.init_caches(SLOTS, BLOCKS * PAGE, page_spec=spec)))
    pool_bytes = sum(a.size * a.dtype.itemsize
                     for a in jax.tree.leaves(caches))
    rows, fn = ((SLOTS, 1), make_serve_step(model)) if step == "decode" \
        else ((1, CHUNK), make_chunk_step(model))
    batch = {"tokens": rows, "block_tables": (rows[0], BLOCKS)}
    batch.update({"pos": (SLOTS,)} if step == "decode" else
                 {k: (1,) for k in ("offset", "valid", "stage_base")})
    batch = {k: jax.ShapeDtypeStruct(v, i32, sharding=one_chip)
             for k, v in batch.items()}
    compiled = jax.jit(fn, donate_argnums=(2,)).lower(
        params, batch, caches).compile()
    pool = re.compile(r"= \w+\[[\d,]*\b%d,%d," % (spec.num_pages, PAGE))
    moves = re.compile(r"\b(copy|dynamic-slice|dynamic-update-slice)\(")
    bad = [ln.strip() for ln in compiled.as_text().splitlines()
           if pool.search(ln) and moves.search(ln)]
    assert not bad, "\n".join(ln[:160] for ln in bad[:4])
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes
