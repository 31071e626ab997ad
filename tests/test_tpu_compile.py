"""The main-path Pallas kernels compile for a TPU v5e at ternary-paper widths
(K=1024, N=4096, d_ff 4096, 16 heads of 64, bf16).

Nothing runs: each test lowers a kernel for a *described* v5e:2x2 topology
and compiles it with the TPU compiler, which refuses what interpret mode
accepts (3-D vector ops, 8-bit iotas, casts Mosaic lacks, too much VMEM).
The topology is described inside a module fixture, never at import, so
every test worker collects the same tests and only the one running this
file loads the TPU library; where it cannot be described, the tests skip.

Each compiled kernel's instruction name (its ``pallas_call`` name) is also
checked against the benchmark's kernel classes (``bench/kernels/``), the
patterns a device trace's kernel time is summed by.
"""
import glob
import importlib
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import weights
from repro.kernels import autotune, ops

tg = importlib.import_module("repro.kernels.ternary_gemm")
fm = importlib.import_module("repro.kernels.fused_mlp")
bp = importlib.import_module("repro.kernels.ternary_gemm_bitplane")
pk = importlib.import_module("repro.paging.kernels")

K, N, FF = 1024, 4096, 4096
BF16, U32 = jnp.bfloat16, jnp.uint32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def sds(topo):
    one = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


KERNEL_CLASSES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              os.pardir, "bench", "kernels")


def _kernel_classes(compiled):
    """The class of each Pallas call in ``compiled``: the first class file
    (in name order) with a pattern in the call's instruction name."""
    classes = []
    for f in sorted(glob.glob(os.path.join(KERNEL_CLASSES, "*.json"))):
        with open(f) as fh:
            pats = json.load(fh)["patterns"]
        classes.append((os.path.basename(f)[:-5], re.compile("|".join(pats))))
    names = [re.match(r"\s*(?:ROOT\s+)?%?([^\s=]+)\s*=", ln).group(1)
             for ln in compiled.as_text().splitlines()
             if "custom_call_target=\"tpu_custom_call\"" in ln]
    assert names
    return {next((c for c, pat in classes if pat.search(n)), None)
            for n in names}


# every block shape the autotuner can pick (decode widens the grid)
BLOCKS = sorted(set(autotune.CANDIDATE_BLOCKS
                    + autotune.DECODE_CANDIDATE_BLOCKS))


@pytest.mark.parametrize("bm,bn,bk", BLOCKS)
def test_dense_compiles_at_autotuner_blocks(sds, bm, bn, bk):
    """M = block_m: 8 and 16 are the decode GEMVs, 64+ prefill."""
    def f(x, w, s):
        return tg.ternary_gemm_pallas(x, w, s, None, block_m=bm, block_n=bn,
                                      block_k=bk, interpret=False)
    compiled = _compile(f, sds((bm, K), BF16), sds((K // 16, N), U32),
                        sds((N,), jnp.float32))
    assert _kernel_classes(compiled) == {"gemm"}


@pytest.mark.parametrize("m", [8, 128])
@pytest.mark.parametrize("kernel", ["skip", "skip_db"])
def test_skip_compiles(sds, kernel, m):
    bn, bk = 128, 256            # weights.pack's default Tiled tiles
    fn = (tg.ternary_gemm_skip_db_pallas if kernel == "skip_db"
          else tg.ternary_gemm_skip_pallas)

    def f(x, w, idx, cnt, s):
        return fn(x, w, idx, cnt, s, None, block_m=m, block_n=bn,
                  block_k=bk, interpret=False)
    compiled = _compile(f, sds((m, K), BF16), sds((K // 16, N), U32),
                        sds((N // bn, K // bk), jnp.int32),
                        sds((N // bn,), jnp.int32), sds((N,), jnp.float32))
    assert _kernel_classes(compiled) == {"gemm"}


@pytest.mark.parametrize("m", [8, 128])
def test_fused_mlp_gated_compiles(sds, m):
    def f(x, wi, wo, wg, si, so, sg):
        return fm.fused_mlp_pallas(x, wi, wo, wg, scale_i=si, scale_o=so,
                                   scale_g=sg, n=K, ff=FF, block_m=m,
                                   block_k1=512, block_k2=512,
                                   interpret=False)
    compiled = _compile(f, sds((m, K), BF16), sds((K // 16, FF), U32),
                        sds((FF // 16, K), U32), sds((K // 16, FF), U32),
                        sds((FF,), jnp.float32), sds((K,), jnp.float32),
                        sds((FF,), jnp.float32))
    assert _kernel_classes(compiled) == {"gemm"}


@pytest.mark.parametrize("factorized", [False, True])
def test_bitplane_compiles(sds, factorized):
    def f(x, p, q, s):
        return bp.ternary_gemm_bitplane(x, p, q, s, block_m=128,
                                        block_n=128, block_k=512,
                                        factorized=factorized,
                                        interpret=False)
    compiled = _compile(f, sds((128, K), BF16), sds((K // 8, N), jnp.uint8),
                        sds((K // 8, N), jnp.uint8), sds((N,), jnp.float32))
    assert _kernel_classes(compiled) == {"gemm"}


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_paged_attention_compiles_at_max_len_1024(sds, kv_dtype):
    """VMEM stays bounded as max_len grows: 4 slots x 64 pages of 16."""
    from repro.paging.quant import Int8Pages
    b, h, hd, ps, max_len = 4, 16, 64, 16, 1024
    t = max_len // ps
    pages = (b * t + 1, ps, h, hd)
    q = sds((b, h, hd), BF16)
    table, lengths = sds((b, t), jnp.int32), sds((b,), jnp.int32)
    if kv_dtype == "int8":
        def f(q, kc, ks, vc, vs, bt, ln):
            return pk.paged_decode_attention_pallas(
                q, Int8Pages(kc, ks), Int8Pages(vc, vs), bt, ln,
                interpret=False)
        codes, scales = sds(pages, jnp.int8), sds(pages[:3], jnp.float32)
        compiled = _compile(f, q, codes, scales, codes, scales, table,
                            lengths)
    else:
        def f(q, kp, vp, bt, ln):
            return pk.paged_decode_attention_pallas(q, kp, vp, bt, ln,
                                                    interpret=False)
        compiled = _compile(f, q, sds(pages, BF16), sds(pages, BF16), table,
                            lengths)
    assert _kernel_classes(compiled) == {"attn"}


@pytest.mark.parametrize("b,s", [(32, 32), (8, 128)])
def test_paged_window_attention_compiles_at_mistral_widths(sds, b, s):
    """Chunk windows at Mistral-NeMo widths (32 heads of 128, 8 KV heads,
    128-token pages, 8 a row, bf16): 32 rows of 32 tokens, and 8 rows of
    128, which splits its queries into tiles. The kernel stays in the
    ``attn`` class, beside the decode kernel."""
    h, kv, hd, ps, t = 32, 8, 128, 128, 8
    pages = (b * t + 1, ps, kv, hd)

    def f(q, kp, vp, bt, ln):
        return pk.paged_window_attention_pallas(q, kp, vp, bt, ln,
                                                interpret=False)
    compiled = _compile(f, sds((b, s, h, hd), BF16), sds(pages, BF16),
                        sds(pages, BF16), sds((b, t), jnp.int32),
                        sds((b,), jnp.int32))
    assert _kernel_classes(compiled) == {"attn"}


def test_tp_paged_window_attention_runs_per_shard(topo):
    """Under a 2-way ``"model"`` axis the window kernel runs per shard on
    half the KV heads: q's head axis (the one before ``head_dim``) splits
    with the pages' KV-head axis."""
    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("data", "model"))
    b, s, h, kv, hd, ps, t = 4, 4, 32, 8, 128, 128, 2

    def placed(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    pages = placed((b * t + 1, ps, kv, hd), BF16,
                   P(None, None, "model", None))
    args = (placed((b, s, h, hd), BF16, P(None, None, "model", None)),
            pages, pages, placed((b, t), jnp.int32, P()),
            placed((b,), jnp.int32, P()))
    with ops.tensor_parallel(mesh):
        lowered = jax.jit(lambda *a: ops.paged_window_attention(
            *a, impl="pallas", interpret=False)).lower(*args)
    calls = [ln for ln in lowered.compile().as_text().splitlines()
             if "tpu_custom_call" in ln]
    rows = s * h // kv
    assert any(f"bf16[{b},{kv // 2},{rows},{hd}]" in ln for ln in calls), \
        calls
    assert not any(f"bf16[{b},{kv},{rows},{hd}]" in ln for ln in calls), \
        calls


def test_tp_sharded_packed_linear_runs_per_shard(topo):
    """A column-split packed linear on a 2x2 mesh compiles to a Pallas
    custom call on the per-shard (K, N/2) problem, not on all of N."""
    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("data", "model"))

    def placed(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    w = weights.Dense2Bit(packed=placed((K // 16, N), U32, P(None, "model")),
                          scale=placed((N,), jnp.float32, P("model")),
                          bias=None, shape=(K, N), tp_dim="n")
    x = placed((8, K), BF16, P())
    with ops.tensor_parallel(mesh):
        lowered = jax.jit(lambda x, w: ops.ternary_gemm(
            x, w, interpret=False)).lower(x, w)
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert any(f"u32[{K // 16},{N // 2}]" in ln for ln in calls), calls
    assert not any(f"u32[{K // 16},{N}]" in ln for ln in calls), calls


@pytest.mark.parametrize("k,n,m,bm", [(6144, 2048, 384, 16),
                                      (2048, 6144, 384, 16),
                                      (6144, 2048, 5120, 128)])
def test_moe_expert_gemm_compiles_at_k_exaone_widths(sds, k, n, m, bm):
    """The grouped expert GEMM at K-EXAONE widths (hidden 6144, experts of
    2048, 8 held): gate/up (K 6144, N 2048) and down (K 2048, N 6144) at a
    decode's 32 x 8 pairs in 16-row tiles, and gate/up at a chunk window's
    in 128-row tiles. Its ``pallas_call`` falls in the ``expert`` class,
    not ``gemm``."""
    me = importlib.import_module("repro.kernels.moe_gemm")
    e, tiles = 8, m // bm

    def f(x, w, s, g, r, a):
        return me.moe_expert_gemm_pallas(x, w, s, g, r, a, block_m=bm,
                                         interpret=False)
    compiled = _compile(f, sds((m, k), BF16), sds((e, k // 16, n), U32),
                        sds((e, n), jnp.float32), sds((tiles,), jnp.int32),
                        sds((tiles,), jnp.int32), sds((1,), jnp.int32))
    assert _kernel_classes(compiled) == {"expert"}
