"""Per-kernel allclose sweeps: Pallas (interpret mode) vs pure-jnp oracles."""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import fused_bn, lif_soma, ops, ref
from repro.kernels.spike_matmul import (spike_matmul, spike_matmul_batched,
                                        spike_pack, spike_unpack)

KEY = jax.random.PRNGKey(42)


@pytest.mark.parametrize("t", [1, 2, 4, 6])
@pytest.mark.parametrize("shape", [
    (32, 64),
    # bigger tiles exercise the same kernel at higher interpret cost: slow
    pytest.param((100, 96), marks=pytest.mark.slow),
    pytest.param((256, 128), marks=pytest.mark.slow),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lif_soma_fwd(t, shape, dtype):
    x = (jax.random.normal(KEY, (t, *shape)) * 2).astype(dtype)
    s_k, u_k, m_k = lif_soma.lif_soma_fwd(x, block_m=64, block_d=64)
    s_r, u_r, m_r = ref.lif_soma_fwd_ref(x)
    assert jnp.allclose(s_k, s_r), "spikes mismatch"
    tol = 1e-6 if dtype == jnp.float32 else 2e-2
    assert jnp.allclose(u_k.astype(jnp.float32), u_r.astype(jnp.float32),
                        atol=tol)
    assert jnp.allclose(m_k, m_r)


@pytest.mark.parametrize("t", [1, 4])
@pytest.mark.parametrize("alpha", [0.25, 0.5, 0.9])
def test_lif_soma_bwd_matches_eq12_and_autodiff(t, alpha):
    x = jax.random.normal(KEY, (t, 48, 80)) * 2
    g = jax.random.normal(jax.random.PRNGKey(7), x.shape)
    s_r, u_r, m_r = ref.lif_soma_fwd_ref(x, alpha=alpha)
    dx_k = lif_soma.lif_soma_bwd(g, u_r, s_r, m_r, alpha=alpha,
                                 block_m=32, block_d=32)
    dx_r = ref.lif_soma_bwd_ref(g, u_r, s_r, m_r, alpha=alpha)
    assert jnp.allclose(dx_k, dx_r, atol=1e-5)
    # the GRAD kernel == JAX autodiff through the surrogate scan (eq. 12)
    from repro.core.lif import LIFConfig, lif_scan
    cfg = LIFConfig(alpha=alpha)
    dx_auto = jax.vjp(lambda xs: lif_scan(xs, cfg), x)[1](g)[0]
    assert jnp.allclose(dx_k, dx_auto, atol=1e-5)


def test_lif_soma_op_custom_vjp():
    x = jax.random.normal(KEY, (4, 64, 64))
    g = jax.random.normal(jax.random.PRNGKey(3), x.shape)
    dx = jax.vjp(ops.lif_soma_op, x)[1](g)[0]
    s_r, u_r, m_r = ref.lif_soma_fwd_ref(x)
    assert jnp.allclose(dx, ref.lif_soma_bwd_ref(g, u_r, s_r, m_r), atol=1e-5)


@pytest.mark.parametrize("m,c,k", [(64, 128, 64), (100, 256, 72),
                                   (256, 512, 256), (33, 64, 129)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("rate", [0.0, 0.2, 1.0])
def test_spike_matmul(m, c, k, dtype, rate):
    sp = (jax.random.uniform(KEY, (m, c)) < rate).astype(jnp.float32)
    w = (jax.random.normal(jax.random.PRNGKey(1), (c, k)) / c ** 0.5
         ).astype(dtype)
    out = spike_matmul(sp, w, block_m=64, block_k=64, block_c=64)
    want = ref.spike_matmul_ref(sp, w)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    assert jnp.allclose(out.astype(jnp.float32), want.astype(jnp.float32),
                        atol=tol, rtol=tol)


@pytest.mark.parametrize("g,m,c,k", [(2, 16, 16, 16), (6, 64, 32, 64),
                                     (3, 33, 40, 17)])
@pytest.mark.parametrize("rate", [0.0, 0.3, 1.0])
def test_spike_matmul_batched(g, m, c, k, rate):
    """Batched packed kernel (the attention path) vs a plain einsum."""
    sp = (jax.random.uniform(KEY, (g, m, c)) < rate).astype(jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (g, c, k)) / c ** 0.5
    out = spike_matmul_batched(sp, w, block_m=32, block_k=32, block_c=16)
    want = jnp.einsum("gmc,gck->gmk", sp, w)
    assert jnp.allclose(out, want, atol=1e-5, rtol=1e-5)


def test_spike_bmm_train_op_grads_match_einsum():
    """The packed batched op's custom VJP == autodiff through the einsum
    (the attention parity contract at the op level)."""
    g, m, c, k = 4, 24, 16, 24
    sp = (jax.random.uniform(KEY, (g, m, c)) < 0.4).astype(jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(2), (g, c, k)) / c ** 0.5
    ct = jax.random.normal(jax.random.PRNGKey(3), (g, m, k))

    out_k, vjp_k = jax.vjp(lambda s, ww: ops.spike_bmm_train_op(s, ww), sp, w)
    out_r, vjp_r = jax.vjp(lambda s, ww: jnp.einsum("gmc,gck->gmk", s, ww),
                           sp, w)
    assert jnp.allclose(out_k, out_r, atol=1e-5)
    for a, b in zip(vjp_k(ct), vjp_r(ct)):
        assert jnp.allclose(a, b, atol=1e-5)


def test_spike_pack_roundtrip():
    sp = (jax.random.uniform(KEY, (37, 256)) < 0.3).astype(jnp.float32)
    assert jnp.array_equal(spike_unpack(spike_pack(sp)), sp)
    assert spike_pack(sp).nbytes == sp.shape[0] * sp.shape[1] // 8


@pytest.mark.parametrize("m,d", [(64, 64), (200, 96), (512, 512), (100, 40)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_bn_fwd(m, d, dtype):
    x = (jax.random.normal(KEY, (m, d)) * 3 + 1).astype(dtype)
    gamma = jnp.ones((d,)) * 1.5
    beta = jnp.zeros((d,)) + 0.2
    y_k, mu_k, sq_k = fused_bn.bn_fwd(x, gamma, beta, block_d=32)
    y_r, mu_r, sq_r = ref.bn_fwd_ref(x, gamma, beta)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    assert jnp.allclose(y_k.astype(jnp.float32), y_r.astype(jnp.float32),
                        atol=tol)
    assert jnp.allclose(mu_k, mu_r, atol=1e-5)
    assert jnp.allclose(sq_k, sq_r, atol=1e-5)


@pytest.mark.parametrize("m,block_m", [(1100, 256), (512, 128), (37, 8)])
def test_bn_row_tiled_matches_ref(m, block_m):
    """Statistics accumulated over row blocks — ragged last block
    included — equal the single-block eq. 13-23 oracle."""
    x = jax.random.normal(KEY, (m, 96)) * 2 + 0.5
    gamma = jax.random.uniform(jax.random.PRNGKey(5), (96,)) + 0.5
    beta = jax.random.normal(jax.random.PRNGKey(6), (96,))
    g = jax.random.normal(jax.random.PRNGKey(7), x.shape)
    got = fused_bn.bn_fwd(x, gamma, beta, block_m=block_m)
    want = ref.bn_fwd_ref(x, gamma, beta)
    for a, b in zip(got, want):
        assert jnp.allclose(a, b, atol=1e-5)
    _, mu, sq = want
    got = fused_bn.bn_bwd(g, x, gamma, mu, sq, block_m=block_m)
    for a, b in zip(got, ref.bn_bwd_ref(g, x, gamma, mu, sq)):
        assert jnp.allclose(a, b, atol=1e-4)


@pytest.mark.parametrize("c", [64, 576, 2048, 4096])
def test_spike_pack_bit_planes(c):
    """Byte j of a pack group holds column b*group/8 + j in bit b; groups
    are 1024 columns where they divide C, else all of C."""
    from repro.kernels.spike_matmul import pack_group

    sp = (jax.random.uniform(KEY, (3, c)) < 0.5).astype(jnp.float32)
    g = pack_group(c)
    packed = spike_pack(sp).astype(jnp.int32)
    for col in (0, 1, g // 8 + 3, c - 1):
        grp, within = divmod(col, g)
        byte = grp * (g // 8) + within % (g // 8)
        bit = within // (g // 8)
        assert jnp.array_equal((packed[:, byte] >> bit) & 1,
                               sp[:, col].astype(jnp.int32))


def test_bn_bwd_matches_eq19_23_and_autodiff():
    x = jax.random.normal(KEY, (300, 64)) * 2 + 0.5
    gamma = jax.random.uniform(jax.random.PRNGKey(5), (64,)) + 0.5
    beta = jax.random.normal(jax.random.PRNGKey(6), (64,))
    g = jax.random.normal(jax.random.PRNGKey(7), x.shape)
    _, mu, sq = ref.bn_fwd_ref(x, gamma, beta)
    dx_k, dg_k, db_k = fused_bn.bn_bwd(g, x, gamma, mu, sq, block_d=32)
    dx_r, dg_r, db_r = ref.bn_bwd_ref(g, x, gamma, mu, sq)
    assert jnp.allclose(dx_k, dx_r, atol=1e-5)
    assert jnp.allclose(dg_k, dg_r, atol=1e-4)
    assert jnp.allclose(db_k, db_r, atol=1e-4)
    # eq. 19-23 == autodiff through the forward (S_N term vanishes w/ batch mu)
    dx_a, dg_a, db_a = jax.vjp(
        lambda xx, gm, bt: ref.bn_fwd_ref(xx, gm, bt)[0], x, gamma, beta)[1](g)
    assert jnp.allclose(dx_k, dx_a, atol=1e-4)
    assert jnp.allclose(dg_k.reshape(-1), dg_a, atol=1e-3)
    assert jnp.allclose(db_k.reshape(-1), db_a, atol=1e-4)


def test_bn_train_op_grads():
    x = jax.random.normal(KEY, (128, 32))
    gamma, beta = jnp.ones((32,)), jnp.zeros((32,))

    def loss_k(x, gm, bt):
        return jnp.sum(ops.bn_train_op(x, gm, bt)[0] ** 2)

    def loss_r(x, gm, bt):
        return jnp.sum(ref.bn_fwd_ref(x, gm, bt)[0] ** 2)

    gk = jax.grad(loss_k, argnums=(0, 1, 2))(x, gamma, beta)
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(x, gamma, beta)
    for a, b in zip(gk, gr):
        assert jnp.allclose(a, b, atol=1e-3)
