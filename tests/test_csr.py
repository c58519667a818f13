"""CSR sparse-gradient tests (ref `tests/unit/test_csr.py` + the
engine's sparse embedding-grad path, ref `engine.py:1190-1246`)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.runtime.csr_tensor import CSRTensor, csr_mean_rows
from deepspeed_tpu.runtime.mesh import build_mesh


def _row_sparse(rows=32, cols=8, touched=(1, 5, 17), seed=0):
    rng = np.random.default_rng(seed)
    dense = np.zeros((rows, cols), np.float32)
    for r in touched:
        dense[r] = rng.normal(size=cols)
    return jnp.asarray(dense)


def test_csr_roundtrip():
    dense = _row_sparse()
    csr = CSRTensor(dense, capacity=8)
    np.testing.assert_allclose(np.asarray(csr.to_dense()),
                               np.asarray(dense))
    sparse_size, dense_size = csr.sparse_size()
    assert sparse_size < dense_size


def test_csr_add():
    a = CSRTensor(_row_sparse(touched=(1, 5)), capacity=4)
    b = CSRTensor(_row_sparse(touched=(2, 5), seed=1), capacity=4)
    expected = np.asarray(a.to_dense()) + np.asarray(b.to_dense())
    a.add(b)
    np.testing.assert_allclose(np.asarray(a.to_dense()), expected,
                               rtol=1e-6)


def test_csr_mean_rows_matches_pmean():
    """Inside shard_map, the sparse gather-reduce must equal the dense
    pmean for row-sparse per-device grads."""
    from jax import shard_map
    mesh = build_mesh({"pipe": 1, "data": 8, "model": 1})
    rows, cols = 64, 16
    rng = np.random.default_rng(0)
    # per-device row-sparse grads: each device touches 3 distinct rows
    locals_ = np.zeros((8, rows, cols), np.float32)
    for d in range(8):
        for r in rng.choice(rows, size=3, replace=False):
            locals_[d, r] = rng.normal(size=cols)
    stacked = jnp.asarray(locals_.reshape(8 * rows, cols))

    def sparse_fn(x):
        return csr_mean_rows(x, "data", capacity=3)

    def dense_fn(x):
        return jax.lax.pmean(x, "data")

    out_sparse = shard_map(
        sparse_fn, mesh=mesh, in_specs=P("data"), out_specs=P(),
        check_vma=False)(stacked)
    out_dense = shard_map(
        dense_fn, mesh=mesh, in_specs=P("data"), out_specs=P(),
        check_vma=False)(stacked)
    np.testing.assert_allclose(np.asarray(out_sparse),
                               np.asarray(out_dense), rtol=1e-6,
                               atol=1e-7)


class _EmbeddingClassifier:
    """Untied-embedding model (the reference's CSR scope is
    torch.nn.Embedding grads, which are pure-gather row-sparse —
    a tied LM head would make the grad dense)."""

    VOCAB, DIM, CLASSES = 512, 16, 4

    def __init__(self):
        import flax.linen as nn

        class Mod(nn.Module):
            @nn.compact
            def __call__(self, ids):
                emb = self.param("embedding",
                                 nn.initializers.normal(0.02),
                                 (_EmbeddingClassifier.VOCAB,
                                  _EmbeddingClassifier.DIM))
                h = emb[ids].mean(axis=1)
                return nn.Dense(_EmbeddingClassifier.CLASSES)(h)
        self.module = Mod()

    def init(self, rng, batch):
        return self.module.init(rng, batch["input_ids"])["params"]

    def loss_fn(self, params, batch, rngs=None, deterministic=False):
        logits = self.module.apply({"params": params},
                                   batch["input_ids"])
        labels = batch["input_ids"][:, 0] % self.CLASSES
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[:, None], 1)[:, 0]
        return jnp.mean(logz - gold)

    def sparse_grad_paths(self):
        return ("embedding",)


def _engine(sparse, mesh):
    from deepspeed_tpu import initialize
    model = _EmbeddingClassifier()
    ids = np.random.default_rng(0).integers(
        0, model.VOCAB, (16, 8)).astype(np.int32)
    params = model.init(jax.random.PRNGKey(0), {"input_ids": ids})
    engine, _, _, _ = initialize(
        model=model, model_parameters=params,
        config={"train_micro_batch_size_per_gpu": 16,
                "sparse_gradients": sparse,
                "zero_optimization": {"stage": 0},
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}}},
        mesh=mesh)
    return engine, ids


def test_sparse_path_update_matches_dense(mesh8):
    """End-to-end: training with sparse_gradients on/off produces the
    same losses and parameters (the CSR path changes the communication
    pattern, never the numerics)."""
    e_dense, ids = _engine(False, mesh8)
    e_sparse, _ = _engine(True, mesh8)
    assert e_sparse._use_shardmap_grads
    assert not e_dense._use_shardmap_grads

    for i in range(3):
        ld = e_dense.train_batch(batch={"input_ids": ids[None]})
        ls = e_sparse.train_batch(batch={"input_ids": ids[None]})
    ld, ls = float(jax.device_get(ld)), float(jax.device_get(ls))
    assert abs(ld - ls) < 1e-4, (ld, ls)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(jax.device_get(a)), np.asarray(jax.device_get(b)),
            rtol=1e-4, atol=1e-5),
        jax.device_get(e_dense.state.params),
        jax.device_get(e_sparse.state.params))


def test_sparse_path_uses_all_gather(mesh8):
    """The embedding grad must ride an all-gather of (indices, values),
    not a dense allreduce (the whole point, ref engine.py:1190)."""
    e_sparse, ids = _engine(True, mesh8)
    jaxpr = jax.make_jaxpr(
        lambda p, b, r, s: e_sparse._micro_grad(p, b, r, s, None))(
            e_sparse.state.params, {"input_ids": jnp.asarray(ids)},
            jax.random.PRNGKey(0), jnp.float32(1.0))
    assert "all_gather" in str(jaxpr)
