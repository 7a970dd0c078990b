"""Model zoo shape/training tests (small shapes on the CPU mesh)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest


def test_mnist_convnet_forward(hvd):
    from horovod_tpu.models import MnistConvNet
    m = MnistConvNet()
    x = jnp.zeros((4, 28, 28, 1))
    vars_ = m.init(jax.random.PRNGKey(0), x)
    out = m.apply(vars_, x)
    assert out.shape == (4, 10)


def _forward(m, x, key=0):
    """(variables, eval-mode output) of a convolutional model, its
    init and its forward each ONE program: run op by op, every one of
    a deep model's convolutions compiled alone, twice over."""
    vars_ = jax.jit(lambda key, x: m.init(key, x, train=False))(
        jax.random.PRNGKey(key), x)
    return vars_, jax.jit(lambda v, x: m.apply(v, x, train=False))(
        vars_, x)


@pytest.mark.parametrize("cls_name,depth", [("ResNet50", 50)])
def test_resnet_forward(hvd, cls_name, depth):
    from horovod_tpu import models
    m = getattr(models, cls_name)(num_classes=10, dtype=jnp.float32,
                                  width=16)
    x = jnp.zeros((2, 64, 64, 3))
    vars_, out = _forward(m, x)
    assert out.shape == (2, 10)
    assert "batch_stats" in vars_


def test_sampled_batchnorm_sample1_is_exact_batchnorm(hvd):
    """SampledBatchNorm(sample=1) oracle vs flax nn.BatchNorm, f32:
    identical normalized output AND identical updated running stats in
    train mode; identical output in eval mode. The bandwidth fix
    (docs/mfu.md, BN stats = 37.8 % of the ResNet step) must be exact
    at its no-op setting."""
    import flax.linen as nn
    from horovod_tpu.models.resnet import SampledBatchNorm
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(8, 4, 4, 6), jnp.float32)
    ref = nn.BatchNorm(use_running_average=False, momentum=0.9,
                       epsilon=1e-5, dtype=jnp.float32)
    got = SampledBatchNorm(use_running_average=False, momentum=0.9,
                           epsilon=1e-5, dtype=jnp.float32, sample=1)
    vr = ref.init(jax.random.PRNGKey(0), x)
    vg = got.init(jax.random.PRNGKey(0), x)
    yr, mr = ref.apply(vr, x, mutable=["batch_stats"])
    yg, mg = got.apply(vg, x, mutable=["batch_stats"])
    np.testing.assert_allclose(np.asarray(yr), np.asarray(yg),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(mr["batch_stats"]["mean"]),
        np.asarray(mg["batch_stats"]["mean"]), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(mr["batch_stats"]["var"]),
        np.asarray(mg["batch_stats"]["var"]), rtol=1e-4, atol=1e-5)
    # Eval: running averages drive both.
    er = nn.BatchNorm(use_running_average=True, epsilon=1e-5,
                      dtype=jnp.float32).apply(
        {"params": vr["params"], "batch_stats": mr["batch_stats"]}, x)
    eg = SampledBatchNorm(use_running_average=True, epsilon=1e-5,
                          dtype=jnp.float32).apply(
        {"params": vg["params"], "batch_stats": mg["batch_stats"]}, x)
    np.testing.assert_allclose(np.asarray(er), np.asarray(eg),
                               rtol=1e-5, atol=1e-5)


def test_sampled_batchnorm_sample_slices_stats(hvd):
    """sample=4: statistics equal exact-BN statistics of the first
    B/4 rows (the documented semantics), applied to the WHOLE batch."""
    from horovod_tpu.models.resnet import SampledBatchNorm
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(8, 3, 3, 5), jnp.float32)
    got = SampledBatchNorm(use_running_average=False, sample=4,
                           dtype=jnp.float32)
    v = got.init(jax.random.PRNGKey(0), x)
    y, mut = got.apply(v, x, mutable=["batch_stats"])
    xs = np.asarray(x)[:2].astype(np.float64)
    mean = xs.mean(axis=(0, 1, 2))
    var = (xs * xs).mean(axis=(0, 1, 2)) - mean ** 2
    np.testing.assert_allclose(
        np.asarray(mut["batch_stats"]["mean"]), 0.1 * mean,
        rtol=1e-4, atol=1e-5)   # momentum 0.9 from zeros init
    expect = (np.asarray(x) - mean) / np.sqrt(var + 1e-5)
    np.testing.assert_allclose(np.asarray(y), expect,
                               rtol=1e-4, atol=1e-4)


def test_resnet_bn_sample_trains(hvd):
    """ResNet(bn_sample=4): the train step runs and learns on random
    data — sampled statistics are a training-dynamics change, not a
    correctness break (A/B config `resnet101_bnsample4`)."""
    import optax
    from horovod_tpu import models
    from horovod_tpu.models import make_cnn_train_step
    from horovod_tpu.models.train import init_cnn_state
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(8, 32, 32, 3), jnp.float32)
    y = jnp.asarray(rng.randint(0, 10, (8,)))
    model = models.ResNet(stage_sizes=[1, 1], num_classes=10,
                          width=16, dtype=jnp.float32, bn_sample=4)
    tx = optax.sgd(0.05, momentum=0.9)
    state = init_cnn_state(model, tx, jax.random.PRNGKey(0), x)
    step = make_cnn_train_step(model, tx)
    losses = []
    for _ in range(6):
        state, loss = step(state, (x, y), jax.random.PRNGKey(1))
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses


def test_s2d_stem_matches_plain_stem(hvd):
    """Space-to-depth stem oracle: with the SAME
    parameter tree (s2d is a pure compute-path flag), the s2d model's
    output equals the plain-stem model's on random input, fp32 — the
    MXU-friendly re-pack must be a numerical identity, not an
    approximation."""
    from horovod_tpu import models
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(2, 64, 64, 3), jnp.float32)
    plain = models.ResNet(stage_sizes=[1, 1], num_classes=10,
                          width=16, dtype=jnp.float32)
    s2d = models.ResNet(stage_sizes=[1, 1], num_classes=10,
                        width=16, dtype=jnp.float32, s2d_stem=True)
    # every forward one program, not a compile a primitive
    vars_, a = _forward(plain, x, key=3)
    # Identical param trees: the s2d stem declares the same
    # stem_conv/kernel [7,7,3,F] under the same name.
    vars_s2d = jax.eval_shape(
        lambda: s2d.init(jax.random.PRNGKey(4), x, train=False))
    assert (jax.tree.structure(vars_) == jax.tree.structure(vars_s2d))
    b = jax.jit(lambda v, x: s2d.apply(v, x, train=False))(vars_, x)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-5)
    # Training mode too (BatchNorm batch stats follow the stem output).
    at, bt = (jax.jit(lambda v, x, m=m: m.apply(
        v, x, train=True, mutable=["batch_stats"]))(vars_, x)[0]
        for m in (plain, s2d))
    np.testing.assert_allclose(np.asarray(at), np.asarray(bt),
                               rtol=1e-5, atol=1e-5)
    # Non-multiple-of-4 inputs are a clear error, not silent wrongness.
    with pytest.raises(ValueError, match="divisible by 4"):
        s2d.apply(vars_, jnp.zeros((1, 30, 30, 3)), train=False)


@pytest.mark.parametrize("hw", [75, 64])  # odd (pad 1) and even (pad 0)
def test_inception_s2d_stem_matches_plain(hvd, hw):
    """Inception stem-conv0 space-to-depth re-pack: same parameter
    tree, same outputs as the plain 3x3/s2/VALID conv, fp32 exact."""
    from horovod_tpu.models import InceptionV3
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(1, hw, hw, 3), jnp.float32)
    plain = InceptionV3(num_classes=10, dtype=jnp.float32)
    s2d = InceptionV3(num_classes=10, dtype=jnp.float32, s2d_stem=True)
    # jitted (`_forward`): op by op the 94 convolutions of each forward
    # compiled one by one, four forwards over; the tree of the second
    # model needs no forward at all
    vars_, a = _forward(plain, x)
    assert (jax.tree.structure(vars_) == jax.tree.structure(
        jax.eval_shape(
            lambda: s2d.init(jax.random.PRNGKey(1), x, train=False))))
    b = jax.jit(lambda v, x: s2d.apply(v, x, train=False))(vars_, x)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-5)


def test_vgg16_forward(hvd):
    from horovod_tpu.models import VGG16
    m = VGG16(num_classes=10, dtype=jnp.float32)
    x = jnp.zeros((2, 32, 32, 3))
    _, out = _forward(m, x)
    assert out.shape == (2, 10)


def test_inception_v3_forward(hvd):
    from horovod_tpu.models import InceptionV3
    m = InceptionV3(num_classes=10, dtype=jnp.float32)
    x = jnp.zeros((1, 299, 299, 3))
    _, out = _forward(m, x)
    assert out.shape == (1, 10)


def test_vit_forward_and_patch_contract(hvd):
    from horovod_tpu.models import VisionTransformer
    m = VisionTransformer(num_classes=10, patch=8, num_layers=2,
                          num_heads=4, head_dim=8, dtype=jnp.float32)
    x = jnp.zeros((2, 32, 32, 3))
    vars_ = m.init(jax.random.PRNGKey(0), x, train=False)
    out = m.apply(vars_, x, train=False)
    assert out.shape == (2, 10)
    assert "batch_stats" not in vars_  # pure-transformer: no BN state
    with pytest.raises(ValueError, match="divisible by patch"):
        m.apply(vars_, jnp.zeros((1, 30, 30, 3)), train=False)


def test_vit_bidirectional_attention_not_causal(hvd):
    """ViT blocks are encoder blocks: masking the LAST patch must
    change the logits (causal attention would hide it from earlier
    tokens but GAP+bidirectional must see it everywhere); and the
    blockwise impl must equal the dot (mask-free) baseline."""
    from horovod_tpu.models import VisionTransformer
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(1, 16, 16, 3), jnp.float32)
    kw = dict(num_classes=4, patch=4, num_layers=1, num_heads=2,
              head_dim=8, dtype=jnp.float32)
    blk = VisionTransformer(attn_impl="blockwise", **kw)
    dot = VisionTransformer(attn_impl="dot", **kw)
    vars_ = blk.init(jax.random.PRNGKey(1), x, train=False)
    a = blk.apply(vars_, x, train=False)
    b = dot.apply(vars_, x, train=False)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_vit_tensor_parallel_matches_replicated(hvd):
    """ViT inherits the LM's TP blocks: params sharded over model=2
    (Megatron column/row) produce the same logits as the replicated
    apply."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu.models import VisionTransformer
    from horovod_tpu.parallel.mesh import make_mesh, use
    from horovod_tpu.parallel.tensor import shard_params, unbox
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(4, 16, 16, 3), jnp.float32)
    m = VisionTransformer(num_classes=6, patch=4, num_layers=2,
                          num_heads=4, head_dim=8, dtype=jnp.float32)
    variables = m.init(jax.random.PRNGKey(6), x, train=False)
    ref = m.apply({"params": unbox(variables["params"])}, x,
                  train=False)
    mesh = make_mesh(data=2, model=2, seq=2)
    with use(mesh):
        params = shard_params(mesh, variables["params"])
        xs = jax.device_put(x, NamedSharding(mesh, P("data")))
        out = jax.jit(lambda p, t: m.apply({"params": p}, t,
                                           train=False))(params, xs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5)


def test_vit_train_step_learns(hvd):
    import optax

    from horovod_tpu.models import make_cnn_train_step, VisionTransformer
    from horovod_tpu.models.train import init_cnn_state
    from horovod_tpu.parallel.mesh import make_mesh
    model = VisionTransformer(num_classes=4, patch=8, num_layers=2,
                              num_heads=4, head_dim=8,
                              dtype=jnp.float32)
    tx = optax.adam(1e-3)
    rng = jax.random.PRNGKey(0)
    state = init_cnn_state(model, tx, rng,
                           jnp.zeros((1, 32, 32, 3), jnp.float32))
    # ViT blocks carry TP partition annotations ("model" axis), so the
    # step needs the full-axes mesh (size-1 defaults), not init()'s
    # 1-D data mesh.
    step = make_cnn_train_step(model, tx, mesh=make_mesh(data=8))
    x = np.random.RandomState(0).randn(16, 32, 32, 3).astype(np.float32)
    y = np.arange(16, dtype=np.int32) % 4
    losses = []
    for _ in range(10):
        state, loss = step(state, (x, y), rng)
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses


def test_word2vec_loss_and_sparse_grads(hvd):
    from horovod_tpu.models import Word2Vec
    from horovod_tpu.models.word2vec import embedding_grad_as_slices
    m = Word2Vec(vocab_size=100, embed_dim=16)
    center = jnp.array([1, 2, 3, 4])
    context = jnp.array([2, 3, 4, 5])
    neg = jnp.array([[7, 8], [9, 10], [11, 12], [13, 14]])
    params = m.init(jax.random.PRNGKey(0), center, context, neg)

    def loss(p):
        return m.apply(p, center, context, neg)

    l, g = jax.value_and_grad(loss)(params)
    assert np.isfinite(float(l))
    emb_grad = g["params"]["embeddings"]
    # Only looked-up rows get gradient.
    nz_rows = np.nonzero(np.abs(np.asarray(emb_grad)).sum(axis=1))[0]
    assert set(nz_rows) <= {1, 2, 3, 4}
    slices = embedding_grad_as_slices(emb_grad, center)
    dense = np.asarray(slices.to_dense())
    np.testing.assert_allclose(dense, np.asarray(emb_grad), rtol=1e-6)


def test_embedding_grad_slices_duplicate_and_last_row(hvd):
    """Pad slots must not duplicate any real row's gradient — including
    when touched ids contain duplicates and the last vocab row."""
    from horovod_tpu.models.word2vec import embedding_grad_as_slices
    dense = np.zeros((6, 2), np.float32)
    dense[1] = [3.0, 3.0]
    dense[5] = [1.0, 1.0]
    touched = jnp.array([1, 1, 5])
    slices = embedding_grad_as_slices(jnp.asarray(dense), touched)
    out = np.asarray(slices.to_dense())
    np.testing.assert_allclose(out, dense)


def test_cnn_train_step_runs_and_learns(hvd):
    from horovod_tpu.models import MnistConvNet, make_cnn_train_step
    from horovod_tpu.models.train import init_cnn_state
    model = MnistConvNet(dtype=jnp.float32)
    tx = optax.sgd(0.05)
    rng = jax.random.PRNGKey(0)
    state = init_cnn_state(model, tx, rng, jnp.zeros((1, 28, 28, 1)))
    # MnistConvNet has no BatchNorm; add a ResNet variant below for stats.
    n = hvd.size()
    x = np.random.RandomState(0).randn(n * 4, 28, 28, 1).astype(np.float32)
    y = np.tile(np.arange(8), n * 4 // 8)[:n * 4]
    step = make_cnn_train_step(model, tx)
    losses = []
    for i in range(6):
        state, loss = step(state, (x, y), rng)
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def test_resnet_train_step_updates_batch_stats(hvd):
    from horovod_tpu import models
    from horovod_tpu.models import make_cnn_train_step
    from horovod_tpu.models.train import init_cnn_state
    model = models.ResNet(stage_sizes=[1, 1], num_classes=4, width=8,
                          dtype=jnp.float32)
    tx = optax.sgd(0.01)
    rng = jax.random.PRNGKey(1)
    state = init_cnn_state(model, tx, rng, jnp.zeros((1, 32, 32, 3)))
    # Materialize to host: step() donates the state buffers.
    stats_before = [np.asarray(x)
                    for x in jax.tree.leaves(state["batch_stats"])]
    n = hvd.size()
    x = np.random.RandomState(1).randn(n * 2, 32, 32, 3).astype(np.float32)
    y = np.zeros((n * 2,), np.int32)
    step = make_cnn_train_step(model, tx)
    state, loss = step(state, (x, y), rng)
    assert np.isfinite(float(loss))
    stats_after = jax.tree.leaves(state["batch_stats"])
    changed = any(not np.allclose(np.asarray(a), np.asarray(b))
                  for a, b in zip(stats_before, stats_after))
    assert changed


def test_graft_entry_lowers(hvd):
    """The driver compile-checks `entry()` on the real chip; this
    guards its tracing path (model build, example args, jit lowering)
    on the CPU mesh so a refactor can't silently break the driver's
    only single-chip signal."""
    import os
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import __graft_entry__
    fn, args = __graft_entry__.entry()
    import jax
    jax.jit(fn).lower(*args)  # tracing + lowering; no compile


def test_bert_forward_contract_and_segments(hvd):
    from horovod_tpu.models import BertMLM
    m = BertMLM(vocab_size=64, num_layers=2, num_heads=4, head_dim=8,
                max_len=32, dtype=jnp.float32)
    toks = jnp.zeros((2, 16), jnp.int32)
    vars_ = m.init(jax.random.PRNGKey(0), toks)
    out = m.apply(vars_, toks)
    assert out.shape == (2, 16, 64)
    # segment embeddings are an optional second input
    seg = jnp.concatenate([jnp.zeros((2, 8), jnp.int32),
                           jnp.ones((2, 8), jnp.int32)], axis=1)
    m2 = BertMLM(vocab_size=64, num_layers=2, num_heads=4, head_dim=8,
                 max_len=32, dtype=jnp.float32)
    vars2 = m2.init(jax.random.PRNGKey(0), toks, seg)
    out2 = m2.apply(vars2, toks, seg)
    assert out2.shape == (2, 16, 64)
    assert "segment" in vars2["params"]


def test_bert_bidirectional_context(hvd):
    """MLM is bidirectional: corrupting the LAST token must change the
    logits at the FIRST position (causal attention could not)."""
    from horovod_tpu.models import BertMLM
    m = BertMLM(vocab_size=32, num_layers=1, num_heads=2, head_dim=8,
                max_len=16, dtype=jnp.float32)
    t1 = jnp.asarray([[1, 2, 3, 4, 5, 6, 7, 8]], jnp.int32)
    t2 = t1.at[0, -1].set(9)
    vars_ = m.init(jax.random.PRNGKey(1), t1)
    a = m.apply(vars_, t1)[0, 0]
    b = m.apply(vars_, t2)[0, 0]
    assert float(jnp.abs(a - b).max()) > 1e-6


def test_mlm_batch_80_10_10(hvd):
    """make_mlm_batch follows the corruption rule statistically and
    is_target marks exactly the selected positions."""
    from horovod_tpu.models import make_mlm_batch
    toks = jnp.full((64, 128), 7, jnp.int32)
    corrupted, sel = make_mlm_batch(
        jax.random.PRNGKey(0), toks, vocab_size=100, mask_id=99,
        mask_rate=0.5)
    sel = np.asarray(sel)
    c = np.asarray(corrupted)
    rate = sel.mean()
    assert 0.45 < rate < 0.55
    # unselected positions never change
    assert (c[~sel] == 7).all()
    inside = c[sel]
    mask_frac = (inside == 99).mean()
    keep_frac = (inside == 7).mean()
    assert 0.75 < mask_frac < 0.85
    # kept (10%) plus random tokens that happen to be 7 (~1%)
    assert 0.07 < keep_frac < 0.16


def test_mlm_loss_reduces_only_targets(hvd):
    from horovod_tpu.models import mlm_loss
    logits = jnp.zeros((1, 4, 8))
    logits = logits.at[0, 0, 3].set(10.0)   # confident right at pos 0
    targets = jnp.asarray([[3, 3, 3, 3]], jnp.int32)
    only_first = jnp.asarray([[True, False, False, False]])
    all_pos = jnp.ones((1, 4), bool)
    l1 = float(mlm_loss(logits, targets, only_first))
    l2 = float(mlm_loss(logits, targets, all_pos))
    assert l1 < 0.01          # the confident position alone
    assert l2 > 1.0           # uniform positions pull the mean up


def test_bert_mlm_train_learns(hvd):
    """End-to-end MLM pretraining on a learnable synthetic corpus:
    loss decreases through make_mlm_train_step (GSPMD over the full
    mesh, DP batch sharding)."""
    import optax

    from horovod_tpu.models import BertMLM, make_mlm_train_step
    from horovod_tpu.parallel.mesh import make_mesh, shard_batch
    from horovod_tpu.parallel.tensor import shard_params, unbox
    model = BertMLM(vocab_size=32, num_layers=2, num_heads=4,
                    head_dim=8, max_len=16, dtype=jnp.float32)
    toks = np.stack([(np.arange(16) + s) % 30
                     for s in range(16)]).astype(np.int32)
    tx = optax.adam(5e-3)
    mesh = make_mesh(data=8)
    variables = model.init(jax.random.PRNGKey(0), jnp.asarray(toks))
    params = shard_params(mesh, variables)["params"]
    opt_state = tx.init(unbox(variables["params"]))
    step = make_mlm_train_step(model, tx, mesh)
    toks_sh = shard_batch(mesh, toks)
    losses = []
    for i in range(60):
        params, opt_state, loss = step(params, opt_state, toks_sh,
                                       jax.random.PRNGKey(100 + i))
        losses.append(float(loss))
    # MLM loss is noisy (fresh random masks per step): compare
    # first-5 vs last-5 means rather than endpoints.
    first, last = np.mean(losses[:5]), np.mean(losses[-5:])
    assert last < 0.7 * first, (first, last, losses[::12])


def test_bert_tensor_parallel_matches_replicated(hvd):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu.models import BertMLM
    from horovod_tpu.parallel.mesh import make_mesh, use
    from horovod_tpu.parallel.tensor import shard_params, unbox
    toks = jnp.asarray(
        np.random.RandomState(3).randint(0, 64, (4, 16)))
    m = BertMLM(vocab_size=64, num_layers=2, num_heads=4, head_dim=8,
                max_len=32, dtype=jnp.float32)
    variables = m.init(jax.random.PRNGKey(4), toks)
    ref = m.apply({"params": unbox(variables["params"])}, toks)
    mesh = make_mesh(data=2, model=2, seq=2)
    with use(mesh):
        params = shard_params(mesh, variables["params"])
        ts = jax.device_put(toks, NamedSharding(mesh, P("data")))
        out = jax.jit(lambda p, t: m.apply({"params": p}, t))(params, ts)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-5)


@pytest.mark.parametrize("chunk", [5, 16, 64])
def test_chunked_mlm_loss_matches_plain(hvd, chunk):
    """Fused-head masked CE == plain mlm_loss — value and grads —
    including ragged chunking (S=16 with chunk 5) and chunk > S."""
    from horovod_tpu.models import (BertMLM, chunked_mlm_loss,
                                    make_mlm_batch, mlm_loss)
    from horovod_tpu.parallel.tensor import unbox
    model = BertMLM(vocab_size=48, num_layers=1, num_heads=2,
                    head_dim=8, max_len=16, dtype=jnp.float32)
    toks = jnp.asarray(np.random.RandomState(7).randint(0, 48, (4, 16)))
    params = unbox(jax.jit(model.init)(
        jax.random.PRNGKey(7), toks)["params"])
    corrupted, sel = make_mlm_batch(jax.random.PRNGKey(8), toks,
                                    vocab_size=48, mask_id=47)

    def plain(p):
        return mlm_loss(model.apply({"params": p}, corrupted),
                        toks, sel)

    def chunked(p):
        hidden, embed = model.apply({"params": p}, corrupted,
                                    return_hidden=True)
        return chunked_mlm_loss(hidden, embed, toks, sel, chunk=chunk)

    # one program a side, not a compile a primitive
    la, ga = jax.jit(jax.value_and_grad(plain))(params)
    lb, gb = jax.jit(jax.value_and_grad(chunked))(params)
    np.testing.assert_allclose(float(la), float(lb), rtol=1e-6)
    jax.tree.map(lambda x, y: np.testing.assert_allclose(
        np.asarray(x), np.asarray(y), rtol=2e-5, atol=2e-5), ga, gb)


def test_mlm_train_step_loss_chunk(hvd):
    """make_mlm_train_step(loss_chunk=...) trains identically to the
    plain path given the same rng stream."""
    import optax
    from horovod_tpu.models import BertMLM, make_mlm_train_step
    from horovod_tpu.parallel.mesh import make_mesh, shard_batch
    from horovod_tpu.parallel.tensor import shard_params, unbox
    model = BertMLM(vocab_size=32, num_layers=1, num_heads=2,
                    head_dim=8, max_len=16, dtype=jnp.float32)
    toks = np.stack([(np.arange(16) + s) % 30
                     for s in range(8)]).astype(np.int32)
    mesh = make_mesh(data=8)
    results = []
    for chunk in (None, 8):
        tx = optax.adam(5e-3)
        variables = model.init(jax.random.PRNGKey(0), jnp.asarray(toks))
        params = shard_params(mesh, variables)["params"]
        opt = tx.init(unbox(variables["params"]))
        step = make_mlm_train_step(model, tx, mesh, loss_chunk=chunk)
        ts = shard_batch(mesh, toks)
        for i in range(5):
            params, opt, loss = step(params, opt, ts,
                                     jax.random.PRNGKey(50 + i))
        results.append(float(loss))
    np.testing.assert_allclose(results[0], results[1], rtol=2e-5)
