"""Multi-host formation tests: real 2-process jax.distributed world with
cross-process eager collectives (SURVEY.md §5.8 — the role the reference's
NCCL rendezvous + ProcessGroupNCCL play; reference test pattern:
TestDistBase spawning real trainer processes, test_dist_base.py:943)."""
import os
import socket
import subprocess
import sys
import textwrap

_WORKER = textwrap.dedent("""
    import sys
    import numpy as np
    rank = int(sys.argv[1]); port = sys.argv[2]
    import os
    os.environ["JAX_COORDINATOR_ADDRESS"] = f"127.0.0.1:{port}"
    os.environ["JAX_NUM_PROCESSES"] = "2"
    os.environ["JAX_PROCESS_ID"] = str(rank)

    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist

    env = dist.init_parallel_env({"dp": 2})   # forms the 2-process world
    import jax
    assert jax.process_count() == 2, jax.process_count()
    assert env.world_size == 2 and env.rank == rank

    # all_reduce: each rank contributes rank+1 -> every rank sees 3
    t = paddle.to_tensor(np.full((4,), rank + 1.0, np.float32))
    out = dist.all_reduce(t)
    np.testing.assert_allclose(out.numpy(), 3.0)

    # mean + max modes
    m = dist.all_reduce(paddle.to_tensor(np.float32(rank)),
                        op=dist.ReduceOp.MAX)
    assert float(m.numpy()) == 1.0, m

    # all_gather: both slices visible on every process
    got = dist.all_gather(None, paddle.to_tensor(
        np.full((2,), float(rank), np.float32)))
    vals = [float(g.numpy()[0]) for g in got]
    assert vals == [0.0, 1.0], vals

    # broadcast from rank 1
    b = dist.broadcast(paddle.to_tensor(
        np.full((3,), float(rank * 10), np.float32)), src=1)
    np.testing.assert_allclose(b.numpy(), 10.0)

    # object broadcast: 3 fixed collectives carry pickled payloads
    objs = [{"k": 41}, "hello", list(range(rank + 1))] if rank == 0 \
        else [None, None, None]
    dist.broadcast_object_list(objs, src=0)
    assert objs[0] == {"k": 41} and objs[1] == "hello" and objs[2] == [0]
    outs = []
    dist.scatter_object_list(outs, [f"obj{r}" for r in range(2)], src=0)
    assert outs == [f"obj{rank}"], outs

    # real cross-process barrier
    dist.barrier()

    # reduce: only dst rank sees the reduction
    r = dist.reduce(paddle.to_tensor(
        np.full((2,), rank + 1.0, np.float32)), dst=1)
    want_r = 3.0 if rank == 1 else rank + 1.0
    np.testing.assert_allclose(r.numpy(), want_r)

    # reduce_scatter: my K-block of the summed [N*K] vector
    rs = dist.reduce_scatter(
        None, paddle.to_tensor(
            np.arange(4, dtype=np.float32) + 10 * rank))
    # rank contributions: [0,1,2,3] and [10,11,12,13] -> sum [10,12,14,16]
    np.testing.assert_allclose(
        rs.numpy(), [10.0, 12.0] if rank == 0 else [14.0, 16.0])

    # alltoall_single: chunk j of my vector goes to rank j
    a2a = dist.alltoall_single(None, paddle.to_tensor(
        np.array([rank * 10, rank * 10 + 1], np.float32)))
    np.testing.assert_allclose(
        a2a.numpy(), [0.0, 10.0] if rank == 0 else [1.0, 11.0])

    # scatter: SPMD same-list convention; rank i gets list[i]
    sc = dist.scatter(None, [paddle.to_tensor(
        np.full((2,), float(i * 100), np.float32)) for i in range(2)])
    np.testing.assert_allclose(sc.numpy(), rank * 100.0)

    # alltoall (list form): my chunk j goes to rank j
    outs = dist.alltoall(None, [paddle.to_tensor(
        np.full((3,), float(rank * 10 + j), np.float32))
        for j in range(2)])
    got = [float(o.numpy()[0]) for o in outs]
    assert got == [0.0 + rank, 10.0 + rank], got

    # all_gather_object: real cross-process python objects
    objs = []
    dist.all_gather_object(objs, {"rank": rank, "tag": "x" * (rank + 1)})
    assert [o["rank"] for o in objs] == [0, 1], objs
    assert objs[1]["tag"] == "xx"

    # quantized all-reduce rides the same multi-process adapters
    from paddle_tpu.distributed.quantized import quantized_all_reduce
    qx = np.linspace(-1, 1, 512).astype(np.float32) * (rank + 1)
    q = quantized_all_reduce(paddle.to_tensor(qx.copy()))
    exact = np.linspace(-1, 1, 512) * 3.0
    rel = np.abs(q.numpy() - exact).max() / np.abs(exact).max()
    assert rel < 0.02, rel

    print("MULTIHOST_OK", rank)
""")


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


_WORKER_P2P = textwrap.dedent("""
    import sys
    import numpy as np
    rank = int(sys.argv[1]); port = sys.argv[2]
    import os
    os.environ["JAX_COORDINATOR_ADDRESS"] = f"127.0.0.1:{port}"
    os.environ["JAX_NUM_PROCESSES"] = "2"
    os.environ["JAX_PROCESS_ID"] = str(rank)

    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist

    env = dist.init_parallel_env({"dp": 2})

    # blocking round-trip: 0 -> 1 then 1 -> 0
    # (reference contract: communication/send.py + recv.py)
    if rank == 0:
        dist.send(paddle.to_tensor(np.arange(6, dtype=np.float32)), dst=1)
        back = paddle.to_tensor(np.zeros(6, np.float32))
        dist.recv(back, src=1)
        np.testing.assert_allclose(back.numpy(), np.arange(6) * 2.0)
    else:
        buf = paddle.to_tensor(np.zeros(6, np.float32))
        dist.recv(buf, src=0)
        np.testing.assert_allclose(buf.numpy(), np.arange(6))
        dist.send(paddle.to_tensor(buf.numpy() * 2.0), dst=0)

    # async isend/irecv with Work handles
    if rank == 0:
        w = dist.isend(paddle.to_tensor(np.full((3,), 7.0, np.float32)),
                       dst=1)
        w.wait()
    else:
        buf = paddle.to_tensor(np.zeros(3, np.float32))
        w = dist.irecv(buf, src=0)
        w.wait()
        assert w.is_completed()
        np.testing.assert_allclose(buf.numpy(), 7.0)

    # pp-style microbatch exchange via batch_isend_irecv: each step rank0
    # feeds activations forward, rank1 returns grads (both directions in
    # one batch; reference batch_isend_irecv.py:27)
    for mb in range(3):
        if rank == 0:
            acts = paddle.to_tensor(
                np.full((2, 4), float(mb), np.float32))
            gbuf = paddle.to_tensor(np.zeros((2, 4), np.float32))
            ops = [dist.P2POp(dist.isend, acts, 1),
                   dist.P2POp(dist.irecv, gbuf, 1)]
            for w in dist.batch_isend_irecv(ops): w.wait()
            np.testing.assert_allclose(gbuf.numpy(), mb * 10.0)
        else:
            abuf = paddle.to_tensor(np.zeros((2, 4), np.float32))
            ops = [dist.P2POp(dist.irecv, abuf, 0)]
            for w in dist.batch_isend_irecv(ops): w.wait()
            np.testing.assert_allclose(abuf.numpy(), float(mb))
            grads = paddle.to_tensor(abuf.numpy() * 10.0)
            for w in dist.batch_isend_irecv(
                    [dist.P2POp(dist.isend, grads, 0)]): w.wait()

    # uneven alltoall_single (global_scatter semantics): rank0 sends
    # sizes [1,3], rank1 sends [2,4]
    if rank == 0:
        xin = np.array([0, 100, 101, 102], np.float32)
        got = dist.alltoall_single(None, paddle.to_tensor(xin),
                                   in_split_sizes=[1, 3],
                                   out_split_sizes=[1, 2])
        np.testing.assert_allclose(got.numpy(), [0, 10, 11])
    else:
        xin = np.array([10, 11, 110, 111, 112, 113], np.float32)
        got = dist.alltoall_single(None, paddle.to_tensor(xin),
                                   in_split_sizes=[2, 4],
                                   out_split_sizes=[3, 4])
        np.testing.assert_allclose(
            got.numpy(), [100, 101, 102, 110, 111, 112, 113])

    dist.barrier()
    print("P2P_OK", rank)
""")


_WORKER_MULTIDEV = textwrap.dedent("""
    import sys
    import numpy as np
    rank = int(sys.argv[1]); port = sys.argv[2]
    import os
    os.environ["JAX_COORDINATOR_ADDRESS"] = f"127.0.0.1:{port}"
    os.environ["JAX_NUM_PROCESSES"] = "2"
    os.environ["JAX_PROCESS_ID"] = str(rank)

    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist

    # 2 processes x 4 local devices = dp axis of 8 (the real pod shape:
    # one process drives several chips); contribution = 4 rows
    env = dist.init_parallel_env({"dp": 8})
    import jax
    assert jax.device_count() == 8, jax.device_count()
    local = np.arange(4, dtype=np.float32) + rank * 4   # rows 0-3 / 4-7
    out = dist.all_reduce(paddle.to_tensor(local[:, None]))
    # sum over all 8 rows of [0..7] broadcast to every row
    np.testing.assert_allclose(out.numpy(), 28.0)
    assert out.numpy().shape == (4, 1)
    dist.barrier()
    # object gather under L=4 local device-ranks
    objs = []
    dist.all_gather_object(objs, ("proc", rank))
    assert len(objs) == 8 and objs.count(("proc", 0)) == 4, objs
    print("MULTIDEV_OK", rank)
""")


def _run_pair(worker, tag, devices_per_proc):
    port = _free_port()
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                        f"{devices_per_proc}")
    procs = [subprocess.Popen(
        [sys.executable, "-c", worker, str(r), str(port)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env, cwd=os.path.dirname(os.path.dirname(__file__)))
        for r in range(2)]
    try:
        outs = [p.communicate(timeout=240)[0] for p in procs]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.communicate()
    for r, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {r} failed:\n{out}"
        assert f"{tag} {r}" in out


def test_two_process_world_collectives():
    _run_pair(_WORKER, "MULTIHOST_OK", devices_per_proc=1)


def test_two_process_p2p_send_recv():
    _run_pair(_WORKER_P2P, "P2P_OK", devices_per_proc=1)


def test_two_process_multidevice_rows():
    _run_pair(_WORKER_MULTIDEV, "MULTIDEV_OK", devices_per_proc=4)
