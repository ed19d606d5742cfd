"""Per-kernel validation: Pallas (interpret=True on CPU) vs pure-jnp oracles,
swept over shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


TOL = {jnp.float32: dict(atol=2e-5, rtol=2e-5),
       jnp.bfloat16: dict(atol=3e-2, rtol=3e-2)}


# ----------------------------------------------------------------------
@pytest.mark.parametrize("B,S,H,KV,hd", [
    (1, 128, 4, 4, 64),     # MHA
    (2, 256, 8, 2, 64),     # GQA 4:1
    (1, 512, 4, 1, 128),    # MQA
    (1, 192, 6, 2, 32),     # ragged seq (pad path)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 64), (False, 0)])
def test_flash_attention(B, S, H, KV, hd, dtype, causal, window):
    k0 = jax.random.PRNGKey(42)
    q = rand(jax.random.fold_in(k0, 0), (B, S, H, hd), dtype)
    k = rand(jax.random.fold_in(k0, 1), (B, S, KV, hd), dtype)
    v = rand(jax.random.fold_in(k0, 2), (B, S, KV, hd), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              block_q=64, block_kv=64, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(out.astype(jnp.float32),
                               want.astype(jnp.float32), **TOL[dtype])


@pytest.mark.parametrize("B,L,H,KV,hd,n_splits", [
    (2, 256, 8, 2, 64, 4),
    (1, 512, 4, 4, 128, 8),
    (3, 128, 4, 1, 64, 2),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention(B, L, H, KV, hd, n_splits, dtype):
    k0 = jax.random.PRNGKey(7)
    q = rand(jax.random.fold_in(k0, 0), (B, H, hd), dtype)
    k = rand(jax.random.fold_in(k0, 1), (B, L, KV, hd), dtype)
    v = rand(jax.random.fold_in(k0, 2), (B, L, KV, hd), dtype)
    lengths = jax.random.randint(jax.random.fold_in(k0, 3), (B,), 1, L + 1)
    out = ops.decode_attention(q, k, v, lengths, n_splits=n_splits,
                               interpret=True)
    want = ref.decode_attention_ref(q, k, v, lengths)
    np.testing.assert_allclose(out.astype(jnp.float32),
                               want.astype(jnp.float32), **TOL[dtype])


PAGED_DECODE_SHAPES = [
    (2, 4, 16, 8, 2, 64),    # GQA 4:1
    (1, 3, 32, 4, 4, 128),   # MHA
    (3, 5, 8, 4, 1, 64),     # MQA, small blocks
]


@pytest.mark.parametrize("B,nb_seq,bs,H,KV,hd", PAGED_DECODE_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_attention(B, nb_seq, bs, H, KV, hd, dtype):
    """Kernel gathers K/V through a shuffled block table; must match the
    gather-then-attend reference on the same pool."""
    k0 = jax.random.PRNGKey(13)
    num_blocks = B * nb_seq + 1                 # + reserved null block 0
    q = rand(jax.random.fold_in(k0, 0), (B, H, hd), dtype)
    kp = rand(jax.random.fold_in(k0, 1), (num_blocks, KV, bs, hd), dtype)
    vp = rand(jax.random.fold_in(k0, 2), (num_blocks, KV, bs, hd), dtype)
    # each sequence owns a random disjoint set of physical blocks, in a
    # scrambled order — exactly what a long-lived allocator produces
    perm = np.asarray(jax.random.permutation(jax.random.fold_in(k0, 3),
                                             num_blocks - 1)) + 1
    bt = jnp.asarray(perm.reshape(B, nb_seq), jnp.int32)
    lengths = jax.random.randint(jax.random.fold_in(k0, 4), (B,), 1,
                                 nb_seq * bs + 1)
    out = ops.paged_decode_attention(q, kp, vp, bt, lengths, interpret=True)
    want = ref.paged_decode_attention_ref(q, kp, vp, bt, lengths)
    np.testing.assert_allclose(out.astype(jnp.float32),
                               want.astype(jnp.float32), **TOL[dtype])


def test_paged_decode_matches_dense_decode():
    """A paged cache holding the same tokens as a dense cache produces the
    same attention output (the paged engine's parity in miniature)."""
    k0 = jax.random.PRNGKey(21)
    B, L, H, KV, hd, bs = 2, 64, 4, 2, 32, 16
    nb = L // bs
    q = rand(jax.random.fold_in(k0, 0), (B, H, hd), jnp.float32)
    k = rand(jax.random.fold_in(k0, 1), (B, L, KV, hd), jnp.float32)
    v = rand(jax.random.fold_in(k0, 2), (B, L, KV, hd), jnp.float32)
    lengths = jnp.asarray([L, 23])
    # scatter the dense caches into a pool, sequences interleaved
    kp = jnp.concatenate([jnp.zeros((1, KV, bs, hd))] +
                         [k[b, j * bs:(j + 1) * bs].swapaxes(0, 1)[None]
                          for j in range(nb) for b in range(B)])
    vp = jnp.concatenate([jnp.zeros((1, KV, bs, hd))] +
                         [v[b, j * bs:(j + 1) * bs].swapaxes(0, 1)[None]
                          for j in range(nb) for b in range(B)])
    bt = jnp.asarray([[1 + j * B + b for j in range(nb)]
                      for b in range(B)], jnp.int32)
    out = ops.paged_decode_attention(q, kp, vp, bt, lengths, interpret=True)
    want = ref.decode_attention_ref(q, k, v, lengths)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("B,nb_seq,bs,H,KV,hd", PAGED_DECODE_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_on_stacked_pools_reads_the_layer(B, nb_seq, bs, H, KV,
                                                      hd, dtype):
    """The decode kernel over pools stacked over layers, told layer l,
    equals the per-layer call on ``pool[l]`` — bit for bit: the same
    blocks come in and the math is the same."""
    k0 = jax.random.PRNGKey(29)
    R, num_blocks = 3, B * nb_seq + 1
    q = rand(jax.random.fold_in(k0, 0), (B, H, hd), dtype)
    kp = rand(jax.random.fold_in(k0, 1), (R, num_blocks, KV, bs, hd), dtype)
    vp = rand(jax.random.fold_in(k0, 2), (R, num_blocks, KV, bs, hd), dtype)
    perm = np.asarray(jax.random.permutation(jax.random.fold_in(k0, 3),
                                             num_blocks - 1)) + 1
    bt = jnp.asarray(perm.reshape(B, nb_seq), jnp.int32)
    lengths = jax.random.randint(jax.random.fold_in(k0, 4), (B,), 1,
                                 nb_seq * bs + 1)
    for layer in range(R):
        out = ops.paged_decode_attention(q, kp, vp, bt, lengths,
                                         jnp.int32(layer), interpret=True)
        want = ops.paged_decode_attention(q, kp[layer], vp[layer], bt,
                                          lengths, interpret=True)
        np.testing.assert_array_equal(np.asarray(out, np.float32),
                                      np.asarray(want, np.float32))


def _kv_write_case(k0, R, N, KV, bs, hd, phys, dtype):
    B = len(phys)
    kp = rand(jax.random.fold_in(k0, 0), (R, N, KV, bs, hd), dtype)
    vp = rand(jax.random.fold_in(k0, 1), (R, N, KV, bs, hd), dtype)
    kr = rand(jax.random.fold_in(k0, 2), (B, KV, hd), dtype)
    vr = rand(jax.random.fold_in(k0, 3), (B, KV, hd), dtype)
    off = jax.random.randint(jax.random.fold_in(k0, 4), (B,), 0, bs)
    return kp, vp, jnp.asarray(phys, jnp.int32), off, kr, vr


@pytest.mark.parametrize("phys", [
    [3, 7, 1, 5, 2, 6],          # shuffled real blocks, one row each
    [0, 4, 0, 0, 2, 0],          # idle rows share the null block
])
@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_kv_write_matches_scatter(phys, layer, dtype):
    """The aliased block-write kernel stores exactly what
    ``pool.at[layer, phys, :, off].set(rows)`` stores in every real block,
    and leaves every other layer of the stack untouched.  The null block
    takes junk when rows share it, so it is left out of the comparison."""
    R, N, KV, bs, hd = 3, 8, 2, 16, 128
    kp, vp, ph, off, kr, vr = _kv_write_case(jax.random.PRNGKey(31), R, N,
                                             KV, bs, hd, phys, dtype)
    want_k = kp.at[layer, ph, :, off].set(kr)
    want_v = vp.at[layer, ph, :, off].set(vr)
    got_k, got_v = ops.paged_kv_write(kp, vp, layer, ph, off, kr, vr,
                                      interpret=True)
    for got, want, pool in ((got_k, want_k, kp), (got_v, want_v, vp)):
        got, want, pool = (np.asarray(a, np.float32)
                           for a in (got, want, pool))
        np.testing.assert_array_equal(got[:, 1:], want[:, 1:])
        others = [r for r in range(R) if r != layer]
        np.testing.assert_array_equal(got[others], pool[others])
        if 0 not in phys:
            np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("B,S,nb_seq,bs,H,KV,hd", [
    (2, 4, 4, 16, 8, 2, 64),    # GQA 4:1 (internlm2-style heads)
    (1, 8, 3, 32, 4, 4, 128),   # MHA (gemma-style KV=H)
    (3, 3, 5, 8, 4, 1, 64),     # MQA, small blocks, odd suffix
    (2, 16, 2, 16, 8, 2, 64),   # suffix spanning whole blocks
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_extend_attention(B, S, nb_seq, bs, H, KV, hd, dtype):
    """Extend kernel: S suffix queries at absolute positions pos0+s attend
    through a shuffled block table; must match the gather-then-attend
    reference (dense-extend mask over absolute positions)."""
    k0 = jax.random.PRNGKey(17)
    num_blocks = B * nb_seq + 1
    q = rand(jax.random.fold_in(k0, 0), (B, S, H, hd), dtype)
    kp = rand(jax.random.fold_in(k0, 1), (num_blocks, KV, bs, hd), dtype)
    vp = rand(jax.random.fold_in(k0, 2), (num_blocks, KV, bs, hd), dtype)
    perm = np.asarray(jax.random.permutation(jax.random.fold_in(k0, 3),
                                             num_blocks - 1)) + 1
    bt = jnp.asarray(perm.reshape(B, nb_seq), jnp.int32)
    # pos0 anywhere the suffix still fits in the table's span — including
    # 0 (pure prefill) when it does
    pos0 = jax.random.randint(jax.random.fold_in(k0, 4), (B,), 0,
                              nb_seq * bs - S + 1)
    out = ops.paged_extend_attention(q, kp, vp, bt, pos0, interpret=True)
    want = ref.paged_extend_attention_ref(q, kp, vp, bt, pos0)
    np.testing.assert_allclose(out.astype(jnp.float32),
                               want.astype(jnp.float32), **TOL[dtype])


def test_paged_extend_matches_dense_flash_prefill():
    """With pos0=0 and the suffix covering the whole sequence, the paged
    extend kernel is causal prefill: it must match the dense flash oracle
    on the same tokens scattered into a pool."""
    k0 = jax.random.PRNGKey(23)
    B, S, H, KV, hd, bs = 2, 64, 4, 2, 32, 16
    nb = S // bs
    q = rand(jax.random.fold_in(k0, 0), (B, S, H, hd), jnp.float32)
    k = rand(jax.random.fold_in(k0, 1), (B, S, KV, hd), jnp.float32)
    v = rand(jax.random.fold_in(k0, 2), (B, S, KV, hd), jnp.float32)
    kp = jnp.concatenate([jnp.zeros((1, KV, bs, hd))] +
                         [k[b, j * bs:(j + 1) * bs].swapaxes(0, 1)[None]
                          for j in range(nb) for b in range(B)])
    vp = jnp.concatenate([jnp.zeros((1, KV, bs, hd))] +
                         [v[b, j * bs:(j + 1) * bs].swapaxes(0, 1)[None]
                          for j in range(nb) for b in range(B)])
    bt = jnp.asarray([[1 + j * B + b for j in range(nb)]
                      for b in range(B)], jnp.int32)
    pos0 = jnp.zeros((B,), jnp.int32)
    out = ops.paged_extend_attention(q, kp, vp, bt, pos0, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("N,M,d", [(64, 128, 256), (100, 60, 128),
                                   (128, 128, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pair_score(N, M, d, dtype):
    k0 = jax.random.PRNGKey(3)
    claims = rand(jax.random.fold_in(k0, 0), (N, d), dtype)
    evid = rand(jax.random.fold_in(k0, 1), (M, d), dtype)
    W = rand(jax.random.fold_in(k0, 2), (d, d), jnp.float32) / np.sqrt(d)
    w = rand(jax.random.fold_in(k0, 3), (2 * d,), jnp.float32)
    params = {"W": W, "w": w, "bias": jnp.asarray(0.3)}
    out = ops.pair_score(params, claims, evid, block_n=32, block_m=64,
                         interpret=True)
    want = ref.pair_score_ref(claims, evid, W, w[:d], w[d:], 0.3)
    # accumulation-order differences grow with d; scores are O(sqrt(d))
    tol = dict(atol=5e-4 * np.sqrt(d), rtol=5e-3) \
        if dtype == jnp.float32 else TOL[jnp.bfloat16]
    np.testing.assert_allclose(out, want, **tol)


@pytest.mark.parametrize("B,S,D,N,chunk", [
    (1, 128, 64, 8, 32),
    (2, 100, 128, 16, 64),   # pad path
    (1, 256, 512, 16, 64),
])
def test_ssm_scan(B, S, D, N, chunk):
    k0 = jax.random.PRNGKey(11)
    # realistic stable dynamics: a in (0,1), b small
    # the kernel's lane-dense (B, S, N, D) layout; the recurrence is
    # elementwise, so the oracle takes any layout
    a = jax.nn.sigmoid(rand(jax.random.fold_in(k0, 0), (B, S, N, D),
                            jnp.float32))
    b = rand(jax.random.fold_in(k0, 1), (B, S, N, D), jnp.float32) * 0.1
    h0 = rand(jax.random.fold_in(k0, 2), (B, N, D), jnp.float32)
    from repro.kernels.ssm_scan import ssm_scan_blocked
    hs, hT = ssm_scan_blocked(a, b, h0, chunk=chunk, block_d=min(64, D),
                              interpret=True)
    want_hs, want_hT = ref.ssm_scan_ref(a, b, h0)
    np.testing.assert_allclose(hs, want_hs, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(hT, want_hT, atol=1e-4, rtol=1e-4)


def test_ssm_ops_matches_model_scan():
    """kernels.ops.ssm_scan == models.ssm.selective_scan on random data."""
    from repro.models.ssm import selective_scan
    k0 = jax.random.PRNGKey(5)
    B, S, D, N = 2, 96, 64, 8
    xc = rand(jax.random.fold_in(k0, 0), (B, S, D), jnp.float32)
    dt = jax.nn.softplus(rand(jax.random.fold_in(k0, 1), (B, S, D), jnp.float32))
    Bc = rand(jax.random.fold_in(k0, 2), (B, S, N), jnp.float32)
    Cc = rand(jax.random.fold_in(k0, 3), (B, S, N), jnp.float32)
    A = -jnp.exp(rand(jax.random.fold_in(k0, 4), (D, N), jnp.float32))
    Dd = rand(jax.random.fold_in(k0, 5), (D,), jnp.float32)
    y_k, h_k = ops.ssm_scan(xc, dt, Bc, Cc, A, Dd, chunk=32,
                            block_d=32, interpret=True)
    y_r, h_r = selective_scan(xc, dt, Bc, Cc, A, Dd, chunk=16)
    np.testing.assert_allclose(y_k, y_r, atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(h_k, h_r, atol=1e-3, rtol=1e-3)


def test_flash_kernel_matches_model_flash():
    """Pallas flash == the model's chunked-jnp flash (the dry-run path)."""
    from repro.models.attention import flash_attention_jnp
    k0 = jax.random.PRNGKey(9)
    B, S, H, KV, hd = 1, 256, 8, 4, 64
    q = rand(jax.random.fold_in(k0, 0), (B, S, H, hd), jnp.float32)
    k = rand(jax.random.fold_in(k0, 1), (B, S, KV, hd), jnp.float32)
    v = rand(jax.random.fold_in(k0, 2), (B, S, KV, hd), jnp.float32)
    a = ops.flash_attention(q, k, v, causal=True, block_q=64, block_kv=64,
                            interpret=True)
    b = flash_attention_jnp(q, k, v, causal=True, q_chunk=64, kv_chunk=64)
    np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)


def test_pair_kernel_matches_pipeline_linkscore():
    """Pallas pair_score == svm.link_score_matrix (phase-2 oracle)."""
    from repro.models import svm as svm_mod
    from repro.core.sharding import split_params
    d = 128
    params, _ = split_params(
        {"link": svm_mod.init_link(jax.random.PRNGKey(1), d)})
    link = params["link"]
    claims = jax.random.normal(jax.random.PRNGKey(2), (96, d))
    evid = jax.random.normal(jax.random.PRNGKey(3), (64, d))
    a = ops.pair_score(link, claims, evid, block_n=32, block_m=32,
                       interpret=True)
    b = svm_mod.link_score_matrix(link, claims, evid)
    np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4)
