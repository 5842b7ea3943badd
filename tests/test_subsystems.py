"""inference / static / profiler / incubate / sparse / checkpoint / launch."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn

# the checkout of the system this repo was modelled on: not on every machine
needs_reference = pytest.mark.skipif(
    not os.path.isdir("/root/reference"),
    reason="reads /root/reference, which is not on this machine")


# -- inference predictor ------------------------------------------------------

def test_jit_save_inference_roundtrip(tmp_path):
    from paddle_tpu.inference import Config, create_predictor
    from paddle_tpu.static import InputSpec

    m = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
    prefix = str(tmp_path / "model")
    paddle.jit.save(m, prefix, input_spec=[InputSpec([2, 8], "float32", "x")])
    cfg = Config(prefix)
    cfg.disable_gpu()
    pred = create_predictor(cfg)
    assert pred.get_input_names() == ["x"]
    x = np.random.default_rng(0).normal(size=(2, 8)).astype("float32")
    out = pred.run([x])[0]
    ref = m(paddle.to_tensor(x)).numpy()
    np.testing.assert_allclose(out, ref, atol=1e-5)


def test_predictor_dynamic_batch_and_multi_output(tmp_path):
    from paddle_tpu.inference import Config, create_predictor
    from paddle_tpu.static import InputSpec

    class TwoHead(nn.Layer):
        def __init__(self):
            super().__init__()
            self.a = nn.Linear(8, 4)
            self.b = nn.Linear(8, 2)

        def forward(self, x):
            return self.a(x), self.b(x)

    m = TwoHead()
    prefix = str(tmp_path / "twohead")
    paddle.jit.save(m, prefix, input_spec=[InputSpec([None, 8], "float32", "x")])
    cfg = Config(prefix)
    cfg.disable_gpu()
    pred = create_predictor(cfg)
    assert len(pred.get_output_names()) == 2
    for bs in (1, 3, 7):  # dynamic batch via symbolic export dims
        x = np.random.default_rng(bs).normal(size=(bs, 8)).astype("float32")
        outs = pred.run([x])
        assert outs[0].shape == (bs, 4) and outs[1].shape == (bs, 2)
        np.testing.assert_allclose(outs[0], m(paddle.to_tensor(x))[0].numpy(),
                                   atol=1e-5)


def test_static_save_load_inference_model(tmp_path):
    from paddle_tpu import static

    m = nn.Linear(4, 2)
    prefix = str(tmp_path / "static_model")
    x = static.data("x", [1, 4], "float32")
    static.save_inference_model(prefix, [x], [], layer=m)
    prog, feeds, fetches = static.load_inference_model(prefix)
    exe = static.Executor()
    xin = np.ones((1, 4), np.float32)
    out = exe.run(prog, feed={"x": xin})[0]
    ref = m(paddle.to_tensor(xin)).numpy()
    np.testing.assert_allclose(out, ref, atol=1e-5)


# -- profiler -----------------------------------------------------------------

def test_profiler_records_and_summarizes(capsys):
    import paddle_tpu.profiler as profiler

    p = profiler.Profiler(timer_only=True)
    p.start()
    # a RecordEvent's duration is kept only while a Profiler records
    with profiler.RecordEvent("unit_test_event"):
        _ = paddle.matmul(paddle.randn([8, 8]), paddle.randn([8, 8]))
    p.step()
    p.step()
    p.stop()
    assert "avg step time" in p.step_info()
    table = p.summary()
    assert "unit_test_event" in table


# -- incubate -----------------------------------------------------------------

def test_fused_transformer_encoder_layer():
    from paddle_tpu.incubate.nn import FusedTransformerEncoderLayer

    layer = FusedTransformerEncoderLayer(32, 4, 64, dropout_rate=0.0)
    x = paddle.randn([2, 8, 32])
    y = layer(x)
    assert y.shape == [2, 8, 32]
    y.sum().backward()


def test_swiglu():
    from paddle_tpu.incubate.nn.functional import swiglu

    x = paddle.randn([4, 8])
    y = paddle.randn([4, 8])
    out = swiglu(x, y)
    ref = (x.numpy() / (1 + np.exp(-x.numpy()))) * y.numpy()
    np.testing.assert_allclose(out.numpy(), ref, rtol=1e-5)


def test_moe_layer_gates():
    from paddle_tpu.incubate.distributed.models.moe import MoELayer

    x = paddle.randn([2, 8, 32])
    for gate in ("gshard", "switch", "naive"):
        moe = MoELayer(d_model=32, d_hidden=64, num_expert=4, top_k=2,
                       gate=gate)
        y = moe(x)
        assert y.shape == [2, 8, 32]
        if gate != "naive":
            assert float(moe.gate.loss) > 0
        (y.sum()).backward()


# -- sparse -------------------------------------------------------------------

def test_sparse_coo_roundtrip():
    sp = paddle.sparse.sparse_coo_tensor([[0, 1, 2], [1, 0, 2]],
                                         [1.0, 2.0, 3.0], (3, 3))
    dense = sp.to_dense().numpy()
    expect = np.zeros((3, 3), np.float32)
    expect[0, 1], expect[1, 0], expect[2, 2] = 1, 2, 3
    np.testing.assert_allclose(dense, expect)
    assert sp.nnz() == 3


def test_sparse_matmul_and_csr():
    sp = paddle.sparse.sparse_coo_tensor([[0, 1], [1, 0]], [2.0, 3.0], (2, 2))
    d = paddle.to_tensor(np.eye(2, dtype=np.float32))
    out = paddle.sparse.matmul(sp, d).numpy()
    np.testing.assert_allclose(out, [[0, 2], [3, 0]])
    csr = sp.to_sparse_csr()
    assert csr.crows().numpy().tolist() == [0, 1, 2]
    r = paddle.sparse.relu(paddle.sparse.sparse_coo_tensor(
        [[0], [0]], [-1.0], (1, 1)))
    assert r.values().numpy()[0] == 0.0


# -- distributed checkpoint ---------------------------------------------------

def test_checkpoint_roundtrip_with_reshard(tmp_path):
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed.checkpoint import load_state_dict, save_state_dict
    from paddle_tpu.distributed.process_mesh import ProcessMesh
    from paddle_tpu.distributed.placement import Replicate, Shard

    n = jax.device_count()
    mesh_a = ProcessMesh(np.arange(n).reshape(2, n // 2), ["x", "y"])
    mesh_b = ProcessMesh(np.arange(n).reshape(n // 2, 2), ["x", "y"])

    w = paddle.to_tensor(np.arange(32, dtype=np.float32).reshape(8, 4))
    w_sharded = dist.shard_tensor(w, mesh_a, [Shard(0), Replicate()])
    state = {"layer": {"weight": w_sharded}}
    save_state_dict(state, str(tmp_path / "ckpt"))

    # load into a DIFFERENT sharding (reshard-on-load)
    w2 = dist.shard_tensor(paddle.zeros([8, 4]), mesh_b, [Replicate(), Shard(1)])
    target = {"layer": {"weight": w2}}
    load_state_dict(target, str(tmp_path / "ckpt"))
    np.testing.assert_allclose(w2.numpy(), w.numpy())
    # destination sharding preserved
    assert "y" in str(w2._data.sharding.spec)


def test_checkpoint_sharded_files_no_full_gather(tmp_path):
    """VERDICT r2 item 2: save writes per-SHARD files (each 1/n of the
    tensor), never one full-tensor file — the full logical value must not
    materialize on the host."""
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed.checkpoint import save_state_dict
    from paddle_tpu.distributed.checkpoint.metadata import Metadata
    from paddle_tpu.distributed.process_mesh import ProcessMesh
    from paddle_tpu.distributed.placement import Shard

    n = jax.device_count()
    mesh = ProcessMesh(np.arange(n), ["x"])
    w = paddle.to_tensor(np.arange(8 * n * 4, dtype=np.float32
                                   ).reshape(8 * n, 4))
    ws = dist.shard_tensor(w, mesh, [Shard(0)])
    save_state_dict({"w": ws}, str(tmp_path / "ck"))
    md = Metadata.load_dir(str(tmp_path / "ck"))
    shards = md.tensors["w"].shards
    assert len(shards) == n                     # one file per device shard
    for sm in shards:
        assert sm.lengths == [8, 4]             # 1/n of the rows each
        f = np.load(str(tmp_path / "ck" / sm.file))
        assert f.shape == (8, 4)
        np.testing.assert_allclose(
            f, w.numpy()[sm.offsets[0]:sm.offsets[0] + 8])


def test_checkpoint_shard_intersection_reshard(tmp_path):
    """Save row-sharded over n devices, load column-sharded over a
    different mesh: every destination shard is assembled from multiple
    intersecting saved shard files (the reference's get_local_load_files
    intersection, load_state_dict.py)."""
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed.checkpoint import (
        load_state_dict, save_state_dict)
    from paddle_tpu.distributed.process_mesh import ProcessMesh
    from paddle_tpu.distributed.placement import Replicate, Shard

    n = jax.device_count()
    mesh_a = ProcessMesh(np.arange(n), ["x"])
    mesh_b = ProcessMesh(np.arange(n).reshape(n // 2, 2), ["a", "b"])
    w = paddle.to_tensor(
        np.arange(4 * n * 2 * n, dtype=np.float32).reshape(4 * n, 2 * n))
    ws = dist.shard_tensor(w, mesh_a, [Shard(0), Replicate()])
    save_state_dict({"w": ws}, str(tmp_path / "ck"))

    w2 = dist.shard_tensor(paddle.zeros([4 * n, 2 * n]), mesh_b,
                           [Replicate(), Shard(1)])
    load_state_dict({"w": w2}, str(tmp_path / "ck"))
    np.testing.assert_allclose(w2.numpy(), w.numpy())
    assert "b" in str(w2._data.sharding.spec)


def test_checkpoint_async_save(tmp_path):
    from paddle_tpu.distributed.checkpoint import load_state_dict, save_state_dict

    state = {"w": paddle.to_tensor(np.ones((4, 4), np.float32))}
    th = save_state_dict(state, str(tmp_path / "ck2"), async_save=True)
    assert th.result() == str(tmp_path / "ck2")   # re-raises writer errors
    assert th.done()
    with pytest.warns(DeprecationWarning):
        th.join()  # legacy spelling that used to swallow errors
    tgt = {"w": paddle.zeros([4, 4])}
    load_state_dict(tgt, str(tmp_path / "ck2"))
    np.testing.assert_allclose(tgt["w"].numpy(), 1.0)


def test_checkpoint_missing_tensor_raises(tmp_path):
    from paddle_tpu.distributed.checkpoint import load_state_dict, save_state_dict

    save_state_dict({"a": paddle.zeros([2])}, str(tmp_path / "ck3"))
    with pytest.raises(ValueError):
        load_state_dict({"b": paddle.zeros([2])}, str(tmp_path / "ck3"))


# -- launch CLI ---------------------------------------------------------------

def test_launch_single_node(tmp_path):
    import subprocess
    import sys

    script = tmp_path / "train_stub.py"
    script.write_text(
        "import os\n"
        "assert os.environ['PADDLE_TRAINER_ID'] == '0'\n"
        "assert os.environ['PADDLE_TRAINERS_NUM'] == '1'\n"
        "print('LAUNCH_STUB_OK')\n")
    env = dict(os.environ, PYTHONPATH="/root/repo", JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch", str(script)],
        capture_output=True, text=True, timeout=120, env=env)
    assert "LAUNCH_STUB_OK" in out.stdout, out.stderr


def test_rpc_local_and_wire():
    """distributed.rpc: init/sync/async + the socket wire path (reference
    rpc.py init_rpc/rpc_sync/rpc_async over a worker agent)."""
    import operator

    from paddle_tpu.distributed import rpc

    rpc.init_rpc("worker0", rank=0, world_size=1)
    try:
        assert rpc.rpc_sync("worker0", operator.add, args=(2, 3)) == 5
        fut = rpc.rpc_async("worker0", operator.mul, args=(4, 5))
        assert fut.wait() == 20
        info = rpc.get_worker_info("worker0")
        assert info.rank == 0 and rpc.get_current_worker_info() == info
        # exercise the actual TCP wire path against our own agent
        assert rpc._call_remote(info, operator.sub, (9, 4), {}, 10.0) == 5
        # remote exceptions propagate
        import pytest as _pytest

        with _pytest.raises(ZeroDivisionError):
            rpc._call_remote(info, operator.truediv, (1, 0), {}, 10.0)
    finally:
        rpc.shutdown()


def test_config5_unet_bf16_through_predictor(tmp_path):
    """Config 5 (BASELINE): diffusion UNet in bf16 through jit.save ->
    StableHLO -> inference Predictor, batch-dynamic, output parity vs the
    eager model (reference AnalysisPredictor pipeline,
    inference_api.cc:1119)."""
    import paddle_tpu.inference as infer
    from paddle_tpu.jit import save as jit_save
    from paddle_tpu.models.unet import unet_tiny
    from paddle_tpu.static import InputSpec

    paddle.seed(0)
    model = unet_tiny()
    # bf16 deploy precision (reference runs the SD UNet in fp16; bf16 is
    # the TPU-native half precision)
    for _, p in model.named_parameters():
        p._data = p._data.astype(jnp.bfloat16)
    model.eval()

    path = str(tmp_path / "unet" / "model")
    jit_save(model, path, input_spec=[
        InputSpec(["batch", 4, 32, 32], "bfloat16", "latents"),
        InputSpec(["batch"], "float32", "timestep"),
    ])

    config = infer.Config(path)
    config.enable_memory_optim()
    predictor = infer.create_predictor(config)

    rng = np.random.default_rng(0)
    lat = rng.normal(size=(2, 4, 32, 32)).astype("float32")
    ts = np.asarray([10.0, 500.0], "float32")
    names = predictor.get_input_names()
    assert names == ["latents", "timestep"], names
    h_lat = predictor.get_input_handle("latents")
    h_lat.copy_from_cpu(lat)
    predictor.get_input_handle("timestep").copy_from_cpu(ts)
    predictor.run()
    out = predictor.get_output_handle(
        predictor.get_output_names()[0]).copy_to_cpu()
    assert out.shape == (2, 4, 32, 32)
    assert np.isfinite(out.astype("float32")).all()

    # parity vs the eager bf16 model
    ref = model(paddle.to_tensor(lat.astype("float32")).astype("bfloat16"),
                paddle.to_tensor(ts))
    np.testing.assert_allclose(out.astype("float32"),
                               ref.numpy().astype("float32"),
                               rtol=5e-2, atol=1e-1)  # bf16 across two
    # compilation paths (exported vs eager) differs in fusion order

    # dynamic batch: a different batch size without re-export
    h_lat.copy_from_cpu(rng.normal(size=(1, 4, 32, 32)).astype("float32"))
    predictor.get_input_handle("timestep").copy_from_cpu(
        np.asarray([3.0], "float32"))
    predictor.run()
    out1 = predictor.get_output_handle(
        predictor.get_output_names()[0]).copy_to_cpu()
    assert out1.shape == (1, 4, 32, 32)


def test_incubate_fused_ops():
    """fused_layer_norm (multi-axis tail + residual), mmha decode loop with
    RoPE, fused_moe — the incubate fused zoo additions."""
    import paddle_tpu.incubate.nn.functional as IF

    # multi-axis layer norm with flattened 1-D weight (reference layout)
    x = paddle.to_tensor(np.random.default_rng(0).normal(
        size=(2, 3, 4)).astype("float32"))
    w = paddle.to_tensor(np.ones(12, "float32"))
    b = paddle.to_tensor(np.zeros(12, "float32"))
    out = IF.fused_layer_norm(x, w, b, begin_norm_axis=1)
    flat = out.numpy().reshape(2, -1)
    np.testing.assert_allclose(flat.mean(1), 0.0, atol=1e-5)
    np.testing.assert_allclose(flat.std(1), 1.0, atol=1e-2)

    # mmha: greedy 3-step decode with rope; grads flow (apply() dispatch)
    B, H, D, L = 1, 2, 8, 4
    cache = paddle.to_tensor(np.zeros((2, B, H, L, D), "float32"))
    cos = np.ones((L, D), "float32")
    sin = np.zeros((L, D), "float32")
    xq = paddle.to_tensor(np.random.default_rng(1).normal(
        size=(B, 3 * H * D)).astype("float32"))
    xq.stop_gradient = False
    o, cache = IF.masked_multihead_attention(
        xq, cache, seq_len=0, rotary_embs=(paddle.to_tensor(cos),
                                           paddle.to_tensor(sin)))
    assert o.shape == [B, H * D]
    o.sum().backward()
    assert xq.grad is not None

    import pytest as _pytest

    with _pytest.raises(NotImplementedError):
        IF.masked_multihead_attention(xq, cache, seq_len=1, beam_width=2)


def test_fused_moe_and_nan_inf_level():
    import paddle_tpu.incubate.nn.functional as IF

    # fused_moe: output shape, combine weights sum to 1 over chosen experts,
    # grads flow
    E, h, i = 4, 8, 16
    rng = np.random.default_rng(2)
    x = paddle.to_tensor(rng.normal(size=(2, 3, h)).astype("float32"))
    x.stop_gradient = False
    gw = paddle.to_tensor(rng.normal(size=(h, E)).astype("float32"))
    w1 = paddle.to_tensor(rng.normal(size=(E, h, i)).astype("float32"))
    w2 = paddle.to_tensor(rng.normal(size=(E, i, h)).astype("float32"))
    out = IF.fused_moe(x, gw, w1, w2, k=2)
    assert out.shape == [2, 3, h]
    out.sum().backward()
    assert x.grad is not None and np.isfinite(x.grad.numpy()).all()
    # k=1 must equal the single best expert's FFN
    out1 = IF.fused_moe(x, gw, w1, w2, k=1)
    logits = x.numpy().reshape(-1, h) @ gw.numpy()
    best = logits.argmax(-1)
    flat = x.numpy().reshape(-1, h)
    import jax.nn as jnn
    hidden = np.einsum("th,ehi->tei", flat, w1.numpy())
    hidden = np.asarray(jnn.gelu(jnp.asarray(hidden)))
    eo = np.einsum("tei,eih->teh", hidden, w2.numpy())
    manual = eo[np.arange(flat.shape[0]), best]
    np.testing.assert_allclose(out1.numpy().reshape(-1, h), manual,
                               rtol=1e-4, atol=1e-5)

    # FLAGS_check_nan_inf_level > 0: log-only instead of abort
    paddle.set_flags({"FLAGS_check_nan_inf": True,
                      "FLAGS_check_nan_inf_level": 1})
    try:
        paddle.log(paddle.to_tensor(np.array([-1.0], "float32")))  # no raise
    finally:
        paddle.set_flags({"FLAGS_check_nan_inf": False,
                          "FLAGS_check_nan_inf_level": 0})


def test_parameter_server_sparse_training():
    """PS pull/push protocol: local mode trains a toy sparse-embedding
    regression; rpc mode routes the same ops through a worker agent
    (reference distributed/ps pull_sparse/push_sparse pattern)."""
    from paddle_tpu.distributed import ps

    ps.init_server({"emb": {"kind": "sparse", "dim": 4, "lr": 0.5},
                    "w": {"kind": "dense", "shape": (4,), "lr": 0.5}})
    try:
        ids = np.array([3, 7, 3], "int64")
        rows = ps.pull_sparse("emb", ids)
        assert rows.shape == (3, 4)
        np.testing.assert_allclose(rows[0], rows[2])  # same key, same row

        # a few SGD steps on rows toward a target: loss must drop
        target = np.ones((3, 4), "float32")
        losses = []
        for _ in range(20):
            rows = ps.pull_sparse("emb", ids)
            losses.append(float(((rows - target) ** 2).mean()))
            ps.push_sparse("emb", ids, 2 * (rows - target) / rows.size)
        assert losses[-1] < losses[0] * 0.1

        d0 = ps.pull_dense("w")
        ps.push_dense("w", np.ones(4, "float32"))
        np.testing.assert_allclose(ps.pull_dense("w"), d0 - 0.5)
    finally:
        ps.shutdown_server()

    # rpc-routed mode against our own agent
    from paddle_tpu.distributed import rpc

    rpc.init_rpc("ps_server", rank=0, world_size=1)
    try:
        ps.init_server({"emb": {"kind": "sparse", "dim": 2}},
                       server_worker="ps_server")
        rows = ps.pull_sparse("emb", np.array([1, 2], "int64"))
        assert rows.shape == (2, 2)
        ps.push_sparse("emb", np.array([1], "int64"),
                       np.ones((1, 2), "float32"), lr=1.0)
        rows2 = ps.pull_sparse("emb", np.array([1], "int64"))
        np.testing.assert_allclose(rows2[0], rows[0] - 1.0)
    finally:
        ps.shutdown_server()
        rpc.shutdown()


def test_audio_features():
    """paddle.audio: fbank matches librosa-style triangular filters in
    shape/energy; feature layers produce finite outputs; MFCC dct is
    orthonormal."""
    sig = paddle.to_tensor(
        np.sin(np.linspace(0, 200 * np.pi, 2048)).astype("float32")[None])
    spec = paddle.audio.features.Spectrogram(n_fft=256)(sig)
    assert spec.shape == [1, 129, 33]
    lm = paddle.audio.features.LogMelSpectrogram(n_fft=256, n_mels=32,
                                                 top_db=80.0)(sig)
    assert lm.shape == [1, 32, 33]
    v = lm.numpy()
    assert np.isfinite(v).all() and v.max() - v.min() <= 80.0 + 1e-3
    mfcc = paddle.audio.features.MFCC(n_mfcc=13, n_fft=256, n_mels=32)(sig)
    assert mfcc.shape == [1, 13, 33]

    fb = paddle.audio.functional.compute_fbank_matrix(16000, 256, 32).numpy()
    assert fb.shape == (32, 129) and (fb >= 0).all()
    assert (fb.sum(axis=1) > 0).all()  # every filter has support

    dct = paddle.audio.functional.create_dct(13, 32).numpy()
    np.testing.assert_allclose(dct.T @ dct, np.eye(13), atol=1e-5)

    # round-trip of the mel scale
    f = np.array([100.0, 1000.0, 4000.0])
    np.testing.assert_allclose(
        paddle.audio.functional.mel_to_hz(
            paddle.audio.functional.hz_to_mel(f)), f, rtol=1e-6)


def test_to_static_eager_fallback_on_dynamic_control_flow():
    """Tensor-dependent Python control flow degrades to eager with a
    warning instead of crashing (reference SOT fallback semantics)."""
    import warnings

    from paddle_tpu.jit import to_static

    @to_static
    def f(x):
        if float(x.sum()) > 0:  # traced bool -> unconditionally dynamic
            return x * 2
        return x - 1

    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        out = f(paddle.to_tensor(np.array([1.0, 2.0], "float32")))
        out2 = f(paddle.to_tensor(np.array([-5.0, -5.0], "float32")))
    np.testing.assert_allclose(out.numpy(), [2.0, 4.0])
    np.testing.assert_allclose(out2.numpy(), [-6.0, -6.0])
    assert any("control flow" in str(w.message) for w in rec)


def test_audio_wav_roundtrip(tmp_path):
    sig = np.sin(np.linspace(0, 20 * np.pi, 800)).astype("float32")[None]
    p = str(tmp_path / "t.wav")
    paddle.audio.save(p, paddle.to_tensor(sig), 8000)
    meta = paddle.audio.info(p)
    assert meta["sample_rate"] == 8000 and meta["num_frames"] == 800
    back, sr = paddle.audio.load(p)
    assert sr == 8000 and back.shape == [1, 800]
    np.testing.assert_allclose(back.numpy(), sig, atol=1e-3)


def test_bert_attention_mask_semantics():
    """[b, s] 0/1 masks convert to additive logits masks: padded keys must
    not influence outputs of valid positions."""
    from paddle_tpu.models.bert import bert_tiny

    paddle.seed(2)
    model = bert_tiny()
    model.eval()
    ids = np.random.default_rng(0).integers(0, 1024, (2, 8)).astype("int64")
    mask_full = np.ones((2, 8), "int64")
    mask_pad = mask_full.copy()
    mask_pad[:, 6:] = 0

    out_pad = model(paddle.to_tensor(ids),
                    attention_mask=paddle.to_tensor(mask_pad))[0].numpy()
    # changing CONTENT of padded positions must not change valid outputs
    ids2 = ids.copy()
    ids2[:, 6:] = (ids2[:, 6:] + 123) % 1024
    out_pad2 = model(paddle.to_tensor(ids2),
                     attention_mask=paddle.to_tensor(mask_pad))[0].numpy()
    np.testing.assert_allclose(out_pad[:, :6], out_pad2[:, :6], atol=1e-5)
    # and masking must differ from not masking
    out_full = model(paddle.to_tensor(ids),
                     attention_mask=paddle.to_tensor(mask_full))[0].numpy()
    assert not np.allclose(out_full[:, :6], out_pad[:, :6])


def test_metadata_merge_empty_shards_do_not_clobber(tmp_path):
    """Multi-host metadata merge (ADVICE r3 medium): a process that holds no
    replica-0 shard of a tensor writes an empty shards list; merging its file
    LAST (metadata.json sorts after metadata.1.json) must not erase the real
    shards merged earlier."""
    from paddle_tpu.distributed.checkpoint.metadata import (
        Metadata, ShardMetadata, TensorMetadata)

    real = Metadata(tensors={"w": TensorMetadata(
        name="w", shape=[4], dtype="float32",
        shards=[ShardMetadata(file="w.0.npy", offsets=[0], lengths=[4])])})
    empty = Metadata(tensors={"w": TensorMetadata(
        name="w", shape=[4], dtype="float32", shards=[])})
    # process-1 file sorts BEFORE process-0's metadata.json
    real.dump(str(tmp_path / "metadata.1.json"))
    empty.dump(str(tmp_path / "metadata.json"))
    merged = Metadata.load_dir(str(tmp_path))
    assert merged.tensors["w"].shards, "empty entry clobbered real shards"
    assert merged.tensors["w"].shards[0].file == "w.0.npy"


def test_weight_only_int8_predictor(tmp_path):
    """Weight-only int8 inference (VERDICT r3 item 5): jit.save(...,
    quantize='weight_only_int8') stores 2-D matmul weights int8 + scale,
    the exported program dequantizes inline, the Predictor runs it with no
    special mode, and accuracy stays within weight-only error bounds
    (reference: PaddleSlim save_quantized_model -> analysis_predictor
    quant passes)."""
    import pickle

    from paddle_tpu.inference import Config, create_predictor
    from paddle_tpu.static import InputSpec

    m = nn.Sequential(nn.Linear(64, 128), nn.GELU(), nn.Linear(128, 128),
                      nn.GELU(), nn.Linear(128, 32))
    x = np.random.default_rng(0).normal(size=(4, 64)).astype("float32")
    ref = m(paddle.to_tensor(x)).numpy()

    fp = str(tmp_path / "fp32")
    q8 = str(tmp_path / "int8")
    spec = [InputSpec([None, 64], "float32", "x")]
    paddle.jit.save(m, fp, input_spec=spec)
    paddle.jit.save(m, q8, input_spec=spec, quantize="weight_only_int8")

    with open(q8 + ".pdmodel", "rb") as f:
        meta = pickle.load(f)
    assert meta["quantize"] == "weight_only_int8"
    assert len(meta["quantized_keys"]) == 3  # the three Linear weights
    with open(q8 + ".pdiparams", "rb") as f:
        qstate = pickle.load(f)
    for k in meta["quantized_keys"]:
        assert qstate[k].dtype == np.int8
        assert qstate[k + ".__scale__"].dtype == np.float32
    import os

    # int8 weights shrink the params file (biases/scales stay f32)
    assert os.path.getsize(q8 + ".pdiparams") < \
        0.5 * os.path.getsize(fp + ".pdiparams")

    for prefix in (fp, q8):
        cfg = Config(prefix)
        cfg.disable_gpu()
        out = create_predictor(cfg).run([x])[0]
        if prefix == fp:
            np.testing.assert_allclose(out, ref, atol=1e-5)
        else:
            # weight-only int8: per-channel 8-bit rounding error only
            err = np.abs(out - ref).max() / (np.abs(ref).max() + 1e-9)
            assert err < 0.05, f"int8 relative error {err:.4f}"


def test_profiler_statistic_tables():
    """Reference-style aggregated stat tables (VERDICT r3 item 9,
    profiler_statistic.py): a small training run renders Overview / Model /
    Operator summaries with per-op calls/total/avg/max/min/ratio rows and
    honors sort keys and view filters."""
    import paddle_tpu.profiler as profiler
    from paddle_tpu.profiler import SortedKeys, SummaryView

    net = nn.Sequential(nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 4))
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=net.parameters())
    loss_fn = paddle.nn.CrossEntropyLoss()
    x = paddle.randn([8, 16])
    y = paddle.to_tensor(np.zeros((8,), "int64"))

    p = profiler.Profiler(timer_only=True)
    p.start()
    for _ in range(3):
        with profiler.RecordEvent("forward"):
            loss = loss_fn(net(x), y)
        with profiler.RecordEvent("backward"):
            loss.backward()
        with profiler.RecordEvent("optimizer_step"):
            opt.step()
            opt.clear_grad()
        p.step()
    p.stop()

    table = p.summary(sorted_by=SortedKeys.CPUTotal)
    assert "Overview Summary" in table
    assert "Operator Summary" in table
    assert "Model Summary" in table
    assert "linear" in table  # the Linear op rows
    assert "Ratio" in table and "%" in table
    # phase bucketing: forward/backward/optimizer rows present
    assert "forward" in table and "backward" in table \
        and "optimizer" in table

    # ops stop being recorded after stop()
    before = p.summary(views=SummaryView.OperatorView)
    _ = paddle.matmul(paddle.randn([4, 4]), paddle.randn([4, 4]))
    assert p.summary(views=SummaryView.OperatorView) == before

    # view filter: operator-only view drops the overview block
    op_only = p.summary(views=SummaryView.OperatorView)
    assert "Operator Summary" in op_only and "Overview" not in op_only

    # sort keys: CPUMax ordering differs from insertion and parses
    t2 = p.summary(sorted_by=SortedKeys.CPUMax,
                   views=SummaryView.OperatorView)
    assert "sorted by CPUMax" in t2


def test_weight_only_int8_bert_predictor(tmp_path):
    """BERT through the int8 predictor (the VERDICT r3 item-5 done shape):
    MLM logits stay within weight-only quantization error of the fp32
    predictor, and argmax predictions agree on nearly all positions."""
    from paddle_tpu.inference import Config, create_predictor
    from paddle_tpu.models.bert import bert_tiny
    from paddle_tpu.static import InputSpec

    m = bert_tiny(hidden_size=64, num_hidden_layers=2, vocab_size=256,
                  max_position_embeddings=32)
    m.eval()
    ids = np.random.default_rng(0).integers(0, 256, (2, 16)).astype("int32")
    spec = [InputSpec([2, 16], "int32", "input_ids")]

    fp, q8 = str(tmp_path / "fp32"), str(tmp_path / "int8")
    paddle.jit.save(m, fp, input_spec=spec)
    paddle.jit.save(m, q8, input_spec=spec, quantize="weight_only_int8")

    outs = {}
    for tag, prefix in (("fp", fp), ("q8", q8)):
        cfg = Config(prefix)
        cfg.disable_gpu()
        outs[tag] = create_predictor(cfg).run([ids])[0]
    ref, got = outs["fp"], outs["q8"]
    rel = np.abs(got - ref).max() / (np.abs(ref).max() + 1e-9)
    assert rel < 0.1, f"int8 BERT relative error {rel:.4f}"
    agree = (got.argmax(-1) == ref.argmax(-1)).mean()
    assert agree > 0.9, f"argmax agreement {agree:.3f}"


def test_int8_ptq_predictor(tmp_path):
    """Activation-int8 PTQ (VERDICT r4 item 3): jit.save(...,
    quantize='int8_ptq', calib_reader=...) calibrates per-layer input
    scales with min-max observers, exports int8 x int8 -> int32 matmul/conv
    math with folded dequant, and the Predictor matches fp within int8
    error bounds (reference nn/quant/format.py LinearQuanter/Dequanter via
    analysis-predictor int8 passes)."""
    import pickle

    from paddle_tpu.inference import Config, create_predictor
    from paddle_tpu.static import InputSpec

    class ConvLin(nn.Layer):
        def __init__(self):
            super().__init__()
            self.conv = nn.Conv2D(3, 8, 3, stride=1, padding=1)
            self.act = nn.ReLU()
            self.fc = nn.Linear(8 * 8 * 8, 32)

        def forward(self, x):
            h = self.act(self.conv(x))
            return self.fc(paddle.reshape(h, [h.shape[0], -1]))

    paddle.seed(0)
    m = ConvLin()
    rng = np.random.default_rng(0)
    calib = [rng.normal(size=(4, 3, 8, 8)).astype("float32")
             for _ in range(4)]
    x = rng.normal(size=(4, 3, 8, 8)).astype("float32")
    ref = m(paddle.to_tensor(x)).numpy()

    q8 = str(tmp_path / "ptq8")
    spec = [InputSpec([None, 3, 8, 8], "float32", "x")]
    paddle.jit.save(m, q8, input_spec=spec, quantize="int8_ptq",
                    calib_reader=calib)

    # the patch restored the model: eager forward unchanged after save
    np.testing.assert_allclose(m(paddle.to_tensor(x)).numpy(), ref,
                               atol=1e-6)

    with open(q8 + ".pdmodel", "rb") as f:
        meta = pickle.load(f)
    assert meta["quantize"] == "int8_ptq"
    assert set(meta["quantized_keys"]) == {"conv.weight", "fc.weight"}
    with open(q8 + ".pdiparams", "rb") as f:
        qstate = pickle.load(f)
    for k in meta["quantized_keys"]:
        assert qstate[k].dtype == np.int8

    cfg = Config(q8)
    cfg.disable_gpu()
    out = create_predictor(cfg).run([x])[0]
    # int8 activation+weight error: looser than weight-only but bounded
    err = np.abs(out - ref).max() / (np.abs(ref).max() + 1e-9)
    assert err < 0.1, f"int8_ptq relative error {err:.4f}"
    # and it is genuinely quantized — not bit-identical to fp
    assert np.abs(out - ref).max() > 0

    # calib_reader required
    with pytest.raises(ValueError, match="calib_reader"):
        paddle.jit.save(m, str(tmp_path / "bad"), input_spec=spec,
                        quantize="int8_ptq")


def _write_synthetic_xprof(log_dir, run="2026_01_01_00_00_00"):
    """A minimal xprof-format trace.json.gz with TPU-style device lanes."""
    import gzip
    import json

    d = os.path.join(log_dir, "plugins", "profile", run)
    os.makedirs(d, exist_ok=True)
    evs = [
        {"ph": "M", "pid": 9, "name": "process_name",
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "pid": 9, "tid": 1, "name": "thread_name",
         "args": {"name": "XLA Ops"}},
        {"ph": "M", "pid": 9, "tid": 2, "name": "thread_name",
         "args": {"name": "XLA Modules"}},
        {"ph": "M", "pid": 7, "name": "process_name",
         "args": {"name": "/host:CPU"}},
        {"ph": "M", "pid": 7, "tid": 3, "name": "thread_name",
         "args": {"name": "python"}},
        # device per-op lanes (us)
        {"ph": "X", "pid": 9, "tid": 1, "name": "jit_matmul", "ts": 0,
         "dur": 700.0},
        {"ph": "X", "pid": 9, "tid": 1, "name": "jit_matmul", "ts": 800,
         "dur": 300.0},
        {"ph": "X", "pid": 9, "tid": 1, "name": "fusion.1", "ts": 1200,
         "dur": 100.0},
        # whole-module lane: busy time, not per-op
        {"ph": "X", "pid": 9, "tid": 2, "name": "jit_step", "ts": 0,
         "dur": 1500.0},
        # host lane must be ignored
        {"ph": "X", "pid": 7, "tid": 3, "name": "isinstance", "ts": 0,
         "dur": 9999.0},
    ]
    with gzip.open(os.path.join(d, "host.trace.json.gz"), "wt") as f:
        json.dump({"traceEvents": evs}, f)


def test_profiler_device_time_attribution(tmp_path):
    """Per-op DEVICE time from the xprof dump (VERDICT r4 item 8): the
    parser reads the TPU lanes, the Operator table gains a DevTotal
    column, and the Kernel Summary matches the reference's GPU-total
    column."""
    from paddle_tpu import profiler as prof_mod
    from paddle_tpu.profiler import Profiler, SummaryView
    from paddle_tpu.profiler.profiler_statistic import (StatisticData,
                                                        build_table)

    _write_synthetic_xprof(str(tmp_path))
    dev, busy, raw = prof_mod._parse_device_trace(str(tmp_path))
    assert set(dev) == {"jit_matmul", "fusion.1"}
    np.testing.assert_allclose(sum(dev["jit_matmul"]), 1e-3)  # 1000us
    np.testing.assert_allclose(busy, 1.5e-3)  # module lane
    assert all(e["name"] != "isinstance" for e in raw)  # host lane dropped

    data = StatisticData({"matmul": [0.002, 0.001]}, {}, [0.01],
                         device_events=dev, device_total=busy)
    np.testing.assert_allclose(data.device_for_op("matmul"), 1e-3)
    table = build_table(data)
    assert "DevTotal" in table
    assert "Kernel Summary" in table and "jit_matmul" in table
    assert "Device busy (xprof)" in table

    # live session on this backend: host-only trace -> graceful fallback
    p = Profiler(log_dir=str(tmp_path / "live"))
    p.start()
    (paddle.ones([8, 8]) @ paddle.ones([8, 8])).numpy()
    p.step()
    p.stop()
    out = p.summary(views=[SummaryView.OperatorView,
                           SummaryView.KernelView])
    assert "matmul" in out


def test_profiler_chrome_trace_export(tmp_path):
    """export_chrome_tracing writes one chrome://tracing-loadable file
    merging host op dispatches and device lanes (reference
    chrometracing_logger.cc)."""
    import json

    from paddle_tpu.profiler import Profiler, export_chrome_tracing

    out_dir = str(tmp_path / "chrome")
    p = Profiler(log_dir=str(tmp_path / "log"),
                 on_trace_ready=export_chrome_tracing(out_dir, "w0"))
    p.start()
    (paddle.ones([4, 4]) + paddle.ones([4, 4])).numpy()
    p.stop()
    path = os.path.join(out_dir, "w0.json")
    assert os.path.exists(path)
    trace = json.load(open(path))
    names = {e.get("name") for e in trace["traceEvents"]}
    assert "add" in names  # host op dispatch
    cats = {e.get("cat") for e in trace["traceEvents"] if e.get("ph") == "X"}
    assert "op" in cats


@needs_reference
def test_namespace_surface_parity():
    """Every name in the reference's python __all__ for these namespaces
    resolves here (r5 surface sweep: 'a user switching finds everything
    they need')."""
    import ast
    import importlib

    REF = "/root/reference/python/paddle"

    def ref_all(mod):
        p = os.path.join(REF, mod, "__init__.py")
        tree = ast.parse(open(p).read())
        for node in ast.walk(tree):
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    if getattr(t, "id", None) == "__all__":
                        return set(ast.literal_eval(node.value))
        return set()

    for name in ["io", "static", "metric", "amp", "autograd", "sparse",
                 "distribution", "geometric", "jit", "inference",
                 "optimizer", "nn", "nn/functional", "nn/initializer",
                 "vision", "vision/transforms", "vision/models",
                 "vision/datasets", "distributed", "distributed/fleet",
                 "incubate", "audio", "device", "utils", "onnx", "text"]:
        ra = ref_all(name)
        ours = importlib.import_module(
            f"paddle_tpu.{name.replace('/', '.')}")
        missing = sorted(n for n in ra if not hasattr(ours, n))
        assert not missing, f"paddle.{name} missing {missing}"

    # the top level itself: all 441 reference __all__ names resolve
    tree = ast.parse(open(os.path.join(REF, "__init__.py")).read())
    ra = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if getattr(t, "id", None) == "__all__":
                    ra = set(ast.literal_eval(node.value))
    missing = sorted(n for n in ra if not hasattr(paddle, n))
    assert not missing, f"paddle top-level missing {missing}"
    # the inplace variants really mutate in place
    xi = paddle.to_tensor(np.array([4.0], "float32"))
    ref_id = id(xi)
    xi.sqrt_()
    assert id(xi) == ref_id and float(xi.numpy()[0]) == 2.0


def test_double_backward_and_new_optimizers():
    """create_graph double backward (re-taped vjps) + the r5 optimizers
    descend on a quadratic."""
    from paddle_tpu import autograd

    x = paddle.to_tensor([2.0])
    x.stop_gradient = False
    y = x * x * x
    g = paddle.grad([y], [x], create_graph=True)[0]
    np.testing.assert_allclose(g.numpy(), [12.0])
    g2 = paddle.grad([g], [x])[0]
    np.testing.assert_allclose(g2.numpy(), [12.0])  # 6x

    x2 = paddle.to_tensor(np.array([1.0, 2.0], "float32"))
    x2.stop_gradient = False
    z = (x2[0] ** 3 + x2[0] * x2[1] * x2[1]).sum()
    H = autograd.hessian(z, x2)
    np.testing.assert_allclose(H.numpy(), [[6, 4], [4, 2]], atol=1e-5)

    def run(opt_cls, **kw):
        paddle.seed(0)
        layer = nn.Linear(8, 1)
        opt = opt_cls(parameters=layer.parameters(), **kw)
        x = paddle.ones([16, 8])
        first = last = None
        for _ in range(25):
            loss = (layer(x) ** 2).mean()
            loss.backward()
            opt.step()
            opt.clear_grad()
            first = first if first is not None else float(loss)
            last = float(loss)
        return first, last

    for cls, kw in [(paddle.optimizer.Rprop, dict(learning_rate=0.01)),
                    (paddle.optimizer.ASGD,
                     dict(learning_rate=0.05, batch_num=4)),
                    (paddle.optimizer.NAdam, dict(learning_rate=0.05)),
                    (paddle.optimizer.RAdam, dict(learning_rate=0.05))]:
        a, b = run(cls, **kw)
        assert b < a * 0.5, (cls.__name__, a, b)

    paddle.seed(0)
    layer = nn.Linear(4, 1)
    opt = paddle.optimizer.LBFGS(parameters=layer.parameters(),
                                 line_search_fn="strong_wolfe")
    xx = paddle.ones([8, 4])

    def closure():
        loss = (layer(xx) ** 2).mean()
        loss.backward()
        return loss

    l0 = float(closure().numpy())
    loss = opt.step(closure)
    assert float(loss.numpy()) < l0 * 1e-3


def test_jacobian_batch_axis():
    """batch_axis=0 returns the per-sample block-diagonal [B, M, N], not a
    reshape of the dense matrix (review finding)."""
    from paddle_tpu import autograd

    x = paddle.to_tensor(np.array([[1., 2], [3, 4]], "float32"))
    x.stop_gradient = False
    y = x * x  # dy[b,i]/dx[b,j] = diag(2x[b])
    J = autograd.jacobian(y, x, batch_axis=0)
    assert J.shape == [2, 2, 2]
    np.testing.assert_allclose(J.numpy()[0], np.diag([2., 4]), atol=1e-6)
    np.testing.assert_allclose(J.numpy()[1], np.diag([6., 8]), atol=1e-6)


class TestNNSurfaceExtras:
    """r5 final sweep: nn/nn.functional completion (reference
    python/paddle/nn/{__init__,functional/__init__}.py tails)."""

    def test_adaptive_log_softmax_matches_bruteforce(self):
        import jax
        import jax.numpy as jnp

        import paddle_tpu.nn as nn

        als = nn.AdaptiveLogSoftmaxWithLoss(16, 20, [5, 10], head_bias=True)
        x = paddle.randn([6, 16])
        lab = paddle.to_tensor(np.array([0, 2, 5, 9, 14, 19]))
        out, loss = als(x, lab)
        full = als.log_prob(x).numpy()
        picked = full[np.arange(6), lab.numpy()]
        np.testing.assert_allclose(out.numpy(), picked, rtol=1e-4, atol=1e-5)
        assert abs(float(loss) + picked.mean()) < 1e-4
        # log_prob rows are valid distributions
        np.testing.assert_allclose(
            np.exp(full).sum(1), np.ones(6), rtol=1e-4)
        assert als.predict(x).shape == [6]

    def test_rnn_cell_runner_and_masking(self):
        import paddle_tpu.nn as nn

        cell = nn.LSTMCell(8, 16)
        rnn = nn.RNN(cell)
        x = paddle.randn([4, 6, 8])
        out, (h, c) = rnn(x)
        assert out.shape == [4, 6, 16] and h.shape == [4, 16]
        out.sum().backward()
        assert cell.weight_ih.grad is not None
        lens = paddle.to_tensor(np.array([6, 3, 1, 6], dtype="int32"))
        out2, (h2, _) = rnn(x, sequence_length=lens)
        assert float(np.abs(out2.numpy()[1, 3:]).max()) == 0.0
        # masked sample's final state froze at its last alive step
        out_full, _ = rnn(x)
        bi = nn.BiRNN(nn.GRUCell(8, 12), nn.GRUCell(8, 12))
        bo, _ = bi(x)
        assert bo.shape == [4, 6, 24]

    def test_rnn_cell_base_custom_cell(self):
        import paddle_tpu.nn as nn

        class MyCell(nn.RNNCellBase):
            def __init__(self):
                super().__init__()
                self.lin = nn.Linear(8, 8)

            @property
            def state_shape(self):
                return [8]

            def forward(self, x, states=None):
                if states is None:
                    states = self.get_initial_states(x, batch_dim_idx=0)
                h = paddle.tanh(self.lin(x) + states)
                return h, h

        out, st = nn.RNN(MyCell())(paddle.randn([2, 5, 8]))
        assert out.shape == [2, 5, 8] and st.shape == [2, 8]

    def test_dynamic_decode_beam_search(self):
        import paddle_tpu.nn as nn

        emb = nn.Embedding(12, 8)
        dec = nn.BeamSearchDecoder(nn.GRUCell(8, 16), start_token=1,
                                   end_token=2, beam_size=3,
                                   embedding_fn=emb,
                                   output_fn=nn.Linear(16, 12))
        ids, scores, lens = nn.dynamic_decode(
            dec, inits=paddle.zeros([2, 16]), max_step_num=10,
            return_length=True)
        B, K, T = ids.shape
        assert (B, K) == (2, 3) and T <= 10
        assert scores.shape == [2, 3] and lens.shape == [2, 3]
        # beams sorted best-first per batch
        s = scores.numpy()
        assert (np.diff(s, axis=1) <= 1e-6).all()

    def test_inplace_activations_tape(self):
        import paddle_tpu.nn.functional as F

        a = paddle.randn([3, 3])
        a.stop_gradient = False
        b = a * 1.0
        r = F.leaky_relu_(b)
        assert r is b
        r.sum().backward()
        assert a.grad is not None and a.grad.shape == [3, 3]

    def test_new_losses_reduce_and_values(self):
        import paddle_tpu.nn as nn
        import paddle_tpu.nn.functional as F

        x = paddle.zeros([4, 3])
        t = paddle.ones([4, 3])
        # soft margin at logit 0: log(1+e^0) = log 2
        assert abs(float(F.soft_margin_loss(x, t)) - np.log(2)) < 1e-5
        # poisson nll log-input at 0 pred: e^0 - t*0 = 1
        assert abs(float(F.poisson_nll_loss(x, t)) - 1.0) < 1e-5
        # gaussian nll with var=1, pred=label: 0.5*log(1) + 0 = 0
        assert abs(float(F.gaussian_nll_loss(x, x, paddle.ones([4, 3])))) < 1e-5
        assert F.pairwise_distance(x, t).shape == [4]
        # multi margin: hinge on true class 0, margin 1 → (1-0+0)=... all
        # logits equal → margin stays 1 on C-1 wrong classes / C
        lab = paddle.to_tensor(np.zeros(4, dtype="int64"))
        assert abs(float(F.multi_margin_loss(x, lab)) - 2.0 / 3.0) < 1e-5
        assert nn.MultiMarginLoss().kw["margin"] == 1.0

    def test_flashmask_and_sparse_attention(self):
        import paddle_tpu.nn.functional as F

        q = paddle.randn([2, 8, 2, 4])
        # startend rows all = S → nothing masked → equals plain sdpa
        sr = paddle.to_tensor(np.full((2, 2, 8, 1), 8, dtype="int32"))
        out = F.flashmask_attention(q, q, q, startend_row_indices=sr)
        base = F.scaled_dot_product_attention(q, q, q)
        np.testing.assert_allclose(out.numpy(), base.numpy(),
                                   rtol=1e-4, atol=1e-5)
        # dense CSR (every row attends to all cols) == dense attention
        qs = paddle.randn([1, 2, 6, 4])
        off = paddle.to_tensor(
            np.tile(np.arange(0, 7, dtype="int32") * 6, (1, 2, 1)))
        cols = paddle.to_tensor(
            np.tile(np.tile(np.arange(6, dtype="int32"), 6), (1, 2, 1)))
        outs = F.sparse_attention(qs, qs, qs, off, cols)
        # dense reference in bhsd layout
        import jax
        import jax.numpy as jnp

        qd = jnp.asarray(qs.numpy())
        logits = jnp.einsum("bhqd,bhkd->bhqk", qd, qd) / 2.0
        ref = jnp.einsum("bhqk,bhkd->bhqd",
                         jax.nn.softmax(logits, -1), qd)
        np.testing.assert_allclose(outs.numpy(), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)

    def test_parameter_dict(self):
        import paddle_tpu.nn as nn

        pd = nn.ParameterDict({"w": paddle.create_parameter([3, 3],
                                                            "float32")})
        pd["b"] = paddle.create_parameter([2], "float32")
        assert len(pd) == 2 and "w" in pd and "b" in pd
        assert len(list(pd.parameters())) == 2
        assert set(pd.keys()) == {"w", "b"}


class TestFinalSweepSurfaces:
    """r5 final sweep: behavior checks for the namespace-closing batch
    (vision transforms/models, distributed intermediate API, incubate
    optimizers, fleet role/data machinery, audio datasets)."""

    def test_transforms_functional_identities(self):
        import paddle_tpu.vision.transforms.functional as TF

        img = (np.random.default_rng(0).random((12, 14, 3)) * 255
               ).astype("uint8")
        np.testing.assert_array_equal(TF.hflip(img), img[:, ::-1])
        np.testing.assert_array_equal(TF.vflip(img), img[::-1])
        np.testing.assert_array_equal(TF.crop(img, 2, 3, 5, 6),
                                      img[2:7, 3:9])
        # identity parameters leave the image (nearly) unchanged
        for out in (TF.adjust_hue(img, 0.0), TF.adjust_saturation(img, 1.0),
                    TF.rotate(img, 0.0),
                    TF.affine(img, 0, (0, 0), 1.0, (0, 0))):
            assert np.abs(np.asarray(out).astype(int)
                          - img.astype(int)).max() <= 1
        pts = [(0, 0), (13, 0), (13, 11), (0, 11)]
        assert np.abs(TF.perspective(img, pts, pts).astype(int)
                      - img.astype(int)).max() <= 1
        # zero contrast collapses to the mean gray
        flat = TF.adjust_contrast(img, 0.0)
        assert np.ptp(flat.astype(int)) <= 1
        e = TF.erase(img, 1, 2, 3, 4, 9)
        assert (e[1:4, 2:6] == 9).all()

    def test_transform_classes_compose(self):
        import paddle_tpu.vision.transforms as T

        np.random.seed(0)
        img = (np.random.rand(16, 16, 3) * 255).astype("uint8")
        pipe = T.Compose([T.RandomResizedCrop(8),
                          T.ColorJitter(0.2, 0.2, 0.2, 0.1),
                          T.RandomErasing(1.0), T.ToTensor()])
        out = pipe(img)
        assert out.shape == (3, 8, 8)
        g = T.Grayscale(3)(img)
        assert np.asarray(g).shape == (16, 16, 3)

    def test_parallelize_col_row_plans(self):
        import paddle_tpu.distributed as dist

        mesh = dist.ProcessMesh(
            np.arange(jax.device_count()).reshape(2, -1), ["dp", "mp"])

        class MLP(nn.Layer):
            def __init__(self):
                super().__init__()
                self.up = nn.Linear(8, 16)
                self.down = nn.Linear(16, 8)

            def forward(self, x):
                return self.down(self.up(x))

        m = MLP()
        dist.parallelize(m, mesh=mesh, config={"mp_config": {
            "parallelize_plan": {"up": dist.ColWiseParallel(),
                                 "down": dist.RowWiseParallel()}}})
        assert "mp" in str(m.up.weight._data.sharding.spec)
        out = m(paddle.randn([4, 8]))
        out.sum().backward()
        assert m.up.weight.grad is not None
        with pytest.raises(ValueError):
            dist.parallelize(m, mesh=mesh, config={"mp_config": {
                "parallelize_plan": {"nonexistent": dist.ColWiseParallel()}}})
        with pytest.raises(NotImplementedError):
            dist.parallelize(m, mesh=mesh,
                             config={"pp_config": {"split_spec": "x"}})

    def test_shard_optimizer_and_dataloader(self):
        import paddle_tpu.distributed as dist
        from paddle_tpu.io import DataLoader, TensorDataset

        mesh = dist.ProcessMesh(
            np.arange(jax.device_count()).reshape(2, -1), ["dp", "mp"])
        m = nn.Linear(8, 8)
        opt = dist.shard_optimizer(
            paddle.optimizer.AdamW(parameters=m.parameters()),
            dist.ShardingStage1("dp", mesh))
        m(paddle.randn([4, 8])).sum().backward()
        opt.step()
        opt.clear_grad()
        ds = TensorDataset([paddle.randn([8, 8]), paddle.randn([8, 1])])
        dl = dist.shard_dataloader(DataLoader(ds, batch_size=4), mesh)
        xb, _ = next(iter(dl))
        assert "dp" in str(xb._data.sharding.spec)

    def test_dist_model_train_eval(self):
        import paddle_tpu.distributed as dist

        m = nn.Linear(4, 4)
        dm = dist.to_static(m, loss=nn.MSELoss(),
                            optimizer=paddle.optimizer.SGD(
                                parameters=m.parameters()))
        l0 = float(dm(paddle.randn([2, 4]), paddle.randn([2, 4])))
        dm.eval()
        l1 = float(dm(paddle.randn([2, 4]), paddle.randn([2, 4])))
        assert l0 >= 0 and l1 >= 0

    def test_incubate_lookahead_and_model_average(self):
        import paddle_tpu.incubate as inc

        m = nn.Linear(4, 1)
        la = inc.LookAhead(paddle.optimizer.SGD(learning_rate=0.1,
                                                parameters=m.parameters()),
                           alpha=0.5, k=2)
        x = paddle.randn([8, 4])
        y = paddle.randn([8, 1])
        losses = []
        for _ in range(8):
            loss = ((m(x) - y) ** 2).mean()
            loss.backward()
            la.step()
            la.clear_grad()
            losses.append(float(loss))
        assert losses[-1] < losses[0]
        ma = inc.ModelAverage(0.5, parameters=m.parameters(),
                              min_average_window=1, max_average_window=4)
        before = np.asarray(m.weight._data).copy()
        for _ in range(3):
            for p in m.parameters():
                p._data = p._data + 1.0
            ma.step()
        with ma.apply():
            applied = np.asarray(m.weight._data).copy()
        restored = np.asarray(m.weight._data)
        assert not np.allclose(applied, restored)
        np.testing.assert_allclose(restored, before + 3.0)

    def test_fleet_role_maker_and_data_generator(self, monkeypatch):
        import paddle_tpu.distributed.fleet as fleet

        monkeypatch.setenv("TRAINING_ROLE", "TRAINER")
        monkeypatch.setenv("PADDLE_TRAINER_ID", "2")
        monkeypatch.setenv("PADDLE_TRAINERS_NUM", "4")
        rm = fleet.PaddleCloudRoleMaker()
        assert rm.is_worker() and rm.worker_index() == 2
        u = fleet.UtilBase()
        u._set_role_maker(rm)
        shard = u.get_file_shard([f"f{i}" for i in range(10)])
        # 10 files over 4 workers: 3,3,2,2 blocks -> idx 2 gets f6,f7
        assert shard == ["f6", "f7"]

        class G(fleet.MultiSlotDataGenerator):
            def generate_sample(self, line):
                def gen():
                    yield [("click", [1]), ("feat", [3, 4])]

                return gen

        assert G().run_from_memory()[0].strip() == "1 1 2 3 4"

    def test_ps_datasets_roundtrip(self, tmp_path):
        import paddle_tpu.distributed as dist

        p = tmp_path / "part-0"
        p.write_text("1 1 3 3 4 5\n1 0 3 6 7 8\n")
        im = dist.InMemoryDataset()
        im.init(batch_size=2)
        im.set_filelist([str(p)])
        im.load_into_memory()
        assert im.get_memory_data_size() == 2
        (batch,) = list(im)
        assert batch[0] == [[1], [3, 4, 5]]
        qd = dist.QueueDataset()
        qd.init(batch_size=1)
        qd.set_filelist([str(p)])
        assert len(list(qd)) == 2
        with pytest.raises(RuntimeError):
            qd.load_into_memory()

    def test_audio_datasets_and_device_surface(self):
        import paddle_tpu.audio as audio
        import paddle_tpu.device as device

        ds = audio.datasets.ESC50(n_items=4)
        x, y = ds[0]
        assert x.ndim == 1 and 0 <= int(y) < 50
        assert device.is_compiled_with_distribute()
        assert not device.is_compiled_with_ipu()
        with pytest.raises(RuntimeError):
            device.IPUPlace()

    def test_utils_and_onnx_gate(self):
        import paddle_tpu
        import paddle_tpu.onnx
        import paddle_tpu.utils as U

        assert U.require_version("0.0.0")
        with pytest.raises(RuntimeError):
            U.require_version("999.0.0")

        @U.deprecated(update_to="paddle.new_api", since="2.0")
        def old():
            return 42

        import warnings

        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            assert old() == 42
            assert any("deprecated" in str(x.message) for x in w)
        with pytest.raises(NotImplementedError):
            paddle_tpu.onnx.export(None, "x")

    def test_new_vision_models_forward(self):
        import paddle_tpu.vision.models as M

        x = paddle.randn([1, 3, 32, 32])
        m = M.MobileNetV3Small(num_classes=4)
        assert m(x).shape == [1, 4]
        s = M.shufflenet_v2_x0_33(num_classes=4)
        assert s(x).shape == [1, 4]
        rx = M.resnext50_32x4d(num_classes=4, with_pool=True)
        assert rx(x).shape == [1, 4]



@needs_reference
def test_tensor_method_surface_parity():
    """Every reference tensor_method_func name (the x.op() surface,
    `python/paddle/tensor/__init__.py`) is a Tensor method here, and the
    handful without top-level spellings behave."""
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.core.tensor_methods import (
        _METHOD_NAMES, reference_method_names)

    names = reference_method_names()
    assert len(names) > 350
    # the baked import-time list still matches the reference
    assert sorted(set(names)) == sorted(set(_METHOD_NAMES))
    missing = sorted(n for n in names if not hasattr(Tensor, n))
    assert not missing, f"Tensor missing methods {missing}"
    # methods dispatch through the same fns: x.op() == paddle.op(x)
    x = paddle.to_tensor(np.random.default_rng(0).random((4, 3))
                         .astype("float32"))
    np.testing.assert_allclose(x.nanmean().numpy(),
                               paddle.nanmean(x).numpy())
    assert x.rot90().shape == [3, 4]
    assert x.mv(paddle.ones([3])).shape == [4]
    # cholesky_inverse == inv(A) given A's factor
    A = np.random.default_rng(1).random((3, 3)).astype("float32")
    A = A @ A.T + 3 * np.eye(3, dtype="float32")
    L = np.linalg.cholesky(A)
    got = paddle.cholesky_inverse(paddle.to_tensor(L)).numpy()
    np.testing.assert_allclose(got, np.linalg.inv(A), atol=1e-4)
    # svd_lowrank reconstructs a rank-2 matrix
    u = np.random.default_rng(2).random((8, 2)).astype("float32")
    m = u @ u.T
    U, S, V = paddle.svd_lowrank(paddle.to_tensor(m), q=4)
    rec = (U.numpy() * S.numpy()) @ V.numpy().T
    np.testing.assert_allclose(rec, m, atol=1e-4)
    # resize_ / set_ rebind storage and sever history
    t = paddle.to_tensor(np.arange(6, dtype="float32"))
    t.resize_([2, 2])
    assert t.numpy().tolist() == [[0.0, 1.0], [2.0, 3.0]]
    t.set_(paddle.ones([5]))
    assert t.shape == [5] and t._node is None
    # in-place trig through the shared builder
    a = paddle.to_tensor(np.array([1.5], "float32"))
    b = a * 1.0
    b.acosh_()
    np.testing.assert_allclose(b.numpy(), np.arccosh([1.5]), rtol=1e-6)
    # ormqr applies Q implicitly — correct for NON-SQUARE x in all four
    # orientations (checked against the explicitly built full Q)
    import scipy.linalg as sla

    Araw = np.random.default_rng(3).random((5, 3)).astype("float64")
    (h, tau), _ = sla.qr(Araw, mode="raw")
    Q = np.eye(5)
    for i in range(3):
        v = np.zeros(5)
        v[i] = 1
        v[i + 1:] = h[i + 1:, i]
        Q = Q @ (np.eye(5) - tau[i] * np.outer(v, v))
    args = (paddle.to_tensor(h.astype("float32")),
            paddle.to_tensor(tau.astype("float32")))
    y = np.random.default_rng(4).random((5, 2)).astype("float32")
    yr = np.random.default_rng(5).random((2, 5)).astype("float32")
    np.testing.assert_allclose(
        paddle.ormqr(*args, paddle.to_tensor(y)).numpy(), Q @ y, atol=1e-5)
    np.testing.assert_allclose(
        paddle.ormqr(*args, paddle.to_tensor(y), transpose=True).numpy(),
        Q.T @ y, atol=1e-5)
    np.testing.assert_allclose(
        paddle.ormqr(*args, paddle.to_tensor(yr), left=False).numpy(),
        yr @ Q, atol=1e-5)
    np.testing.assert_allclose(
        paddle.ormqr(*args, paddle.to_tensor(yr), left=False,
                     transpose=True).numpy(), yr @ Q.T, atol=1e-5)
    # 0-size resize_ growth zero-fills instead of dividing by zero
    z = paddle.ones([3])
    z.set_()
    z.resize_([2, 2])
    assert z.numpy().tolist() == [[0.0, 0.0], [0.0, 0.0]]


def test_inference_pass_framework(tmp_path):
    """Analysis passes (reference AnalysisConfig::pass_builder,
    `api/paddle_pass_builder.cc`): editable pass list; weight_dedup aliases
    byte-identical weights to ONE device buffer; bf16_weights_pass halves
    parameter HBM with an on-the-fly cast back at run; deleting an
    XLA-built-in pass warns instead of lying."""
    import warnings

    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.inference import Config, create_predictor
    from paddle_tpu.static import InputSpec

    class Tied(nn.Layer):
        def __init__(self):
            super().__init__()
            self.a = nn.Linear(8, 8, bias_attr=False)
            self.b = nn.Linear(8, 8, bias_attr=False)
            self.b.weight.set_value(self.a.weight)  # byte-identical

        def forward(self, x):
            return self.b(self.a(x))

    m = Tied()
    prefix = str(tmp_path / "tied")
    paddle.jit.save(m, prefix, input_spec=[InputSpec([2, 8], "float32", "x")])

    cfg = Config(prefix)
    assert "weight_dedup_pass" in cfg.pass_builder().all_passes()
    assert "xla_fusion" in cfg.pass_builder().all_passes()
    pred = create_predictor(cfg)
    bufs = {id(p) for p in pred._params}
    assert len(bufs) < len(pred._params)  # tied weights share one buffer
    x = np.ones((2, 8), np.float32)
    base = np.asarray(pred.run([x])[0])

    # deleting the dedup pass -> distinct buffers, same numerics
    cfg2 = Config(prefix)
    cfg2.delete_pass("weight_dedup_pass")
    pred2 = create_predictor(cfg2)
    assert len({id(p) for p in pred2._params}) == len(pred2._params)
    np.testing.assert_allclose(np.asarray(pred2.run([x])[0]), base,
                               rtol=1e-6)

    # bf16 weights: storage halves, results close to f32
    cfg3 = Config(prefix)
    cfg3.pass_builder().append_pass("bf16_weights_pass")
    pred3 = create_predictor(cfg3)
    assert all(str(p.dtype) == "bfloat16" for p in pred3._params)
    np.testing.assert_allclose(np.asarray(pred3.run([x])[0]), base,
                               rtol=3e-2, atol=3e-2)

    # built-in XLA passes refuse deletion loudly
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        cfg3.delete_pass("xla_fusion")
    assert any("cannot be deleted" in str(x.message) for x in w)

    with pytest.raises(ValueError):
        cfg3.pass_builder().append_pass("nonexistent_pass")


def test_bf16_and_dedup_passes_compose(tmp_path):
    """ADVICE r5 item 5: bf16_weights_pass + weight_dedup_pass used to
    silently cancel — the per-element astype() created a DISTINCT bf16
    array for each aliased entry, so the id()-keyed device_put re-split the
    tied weights. The cast now runs through an id()-keyed memo: tied params
    must map to the SAME device buffer with both passes on."""
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.inference import Config, create_predictor
    from paddle_tpu.static import InputSpec

    class Tied(nn.Layer):
        def __init__(self):
            super().__init__()
            self.a = nn.Linear(8, 8, bias_attr=False)
            self.b = nn.Linear(8, 8, bias_attr=False)
            self.b.weight.set_value(self.a.weight)

        def forward(self, x):
            return self.b(self.a(x))

    prefix = str(tmp_path / "tied")
    paddle.jit.save(Tied(), prefix,
                    input_spec=[InputSpec([2, 8], "float32", "x")])
    cfg = Config(prefix)
    cfg.pass_builder().append_pass("bf16_weights_pass")
    assert "weight_dedup_pass" in cfg.pass_builder().all_passes()
    pred = create_predictor(cfg)
    assert all(str(p.dtype) == "bfloat16" for p in pred._params)
    assert len({id(p) for p in pred._params}) < len(pred._params), \
        "bf16 cast destroyed the dedup aliasing — tied weights got " \
        "separate device buffers"
    out = pred.run([np.ones((2, 8), np.float32)])[0]
    assert np.isfinite(np.asarray(out)).all()


def test_predictor_outputs_are_lazy_zero_copy(tmp_path):
    """run() must not force a host sync: outputs stay device arrays until
    read (the reference ZeroCopyTensor contract)."""
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.inference import Config, create_predictor
    from paddle_tpu.static import InputSpec

    m = nn.Linear(4, 4)
    prefix = str(tmp_path / "lin")
    paddle.jit.save(m, prefix, input_spec=[InputSpec([2, 4], "float32", "x")])
    pred = create_predictor(Config(prefix))
    out = pred.run([np.ones((2, 4), np.float32)])[0]
    import jax

    assert isinstance(out, jax.Array)  # not yet materialized to host
    h = pred.get_output_handle(pred.get_output_names()[0])
    host = h.copy_to_cpu()
    assert isinstance(host, np.ndarray)
    np.testing.assert_allclose(host, np.asarray(out))
