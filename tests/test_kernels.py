"""The SSD scan kernel: Pallas (interpret=True) vs its jnp oracle and the
exact recurrence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.ssd_scan import ssd_scan


@pytest.mark.parametrize("Q,hd,ds,nc,BH", [(8, 4, 4, 2, 2), (16, 8, 12, 3, 4),
                                           (32, 16, 16, 4, 1)])
def test_ssd_kernel_shapes(Q, hd, ds, nc, BH):
    key = jax.random.PRNGKey(Q * hd)
    x = jax.random.normal(key, (BH, nc, Q, hd))
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 1), (BH, nc, Q)))
    A = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 2), (BH,)))
    Bm = jax.random.normal(jax.random.fold_in(key, 3), (BH, nc, Q, ds))
    Cm = jax.random.normal(jax.random.fold_in(key, 4), (BH, nc, Q, ds))
    y1, h1 = ssd_scan(x, dt, A, Bm, Cm, use_pallas=False)
    y2, h2 = ssd_scan(x, dt, A, Bm, Cm, use_pallas=True)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=3e-5, atol=3e-5)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), rtol=3e-5, atol=3e-5)


def test_ssd_kernel_matches_recurrence():
    """The chunked kernel equals the exact token-by-token SSM recurrence."""
    key = jax.random.PRNGKey(9)
    BH, nc, Q, hd, ds = 2, 2, 8, 4, 6
    x = jax.random.normal(key, (BH, nc, Q, hd))
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 1), (BH, nc, Q)))
    A = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 2), (BH,)))
    Bm = jax.random.normal(jax.random.fold_in(key, 3), (BH, nc, Q, ds))
    Cm = jax.random.normal(jax.random.fold_in(key, 4), (BH, nc, Q, ds))
    y_k, _ = ssd_scan(x, dt, A, Bm, Cm, use_pallas=True)

    # exact recurrence: h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t^T; y = C_t h_t
    def one(bh):
        h = np.zeros((ds, hd))
        ys = []
        xs = np.asarray(x[bh]).reshape(-1, hd)
        dts = np.asarray(dt[bh]).reshape(-1)
        Bs = np.asarray(Bm[bh]).reshape(-1, ds)
        Cs = np.asarray(Cm[bh]).reshape(-1, ds)
        a = float(A[bh])
        for t in range(xs.shape[0]):
            h = np.exp(dts[t] * a) * h + dts[t] * np.outer(Bs[t], xs[t])
            ys.append(Cs[t] @ h)
        return np.stack(ys)

    for bh in range(BH):
        np.testing.assert_allclose(
            np.asarray(y_k[bh]).reshape(-1, hd), one(bh), rtol=1e-4, atol=1e-4
        )
