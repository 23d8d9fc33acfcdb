"""paddle.distributed.rpc tests — reference pattern: rpc unittests spawn
real processes (test_rpc_base.py style; no mock agent)."""
import os
import subprocess
import sys
import textwrap

import numpy as np

_WORKER = textwrap.dedent("""
    import sys
    import numpy as np
    import paddle_tpu.distributed.rpc as rpc

    rank = int(sys.argv[1])
    port = sys.argv[2]

    def add(a, b):
        return a + b

    def matmul_np(x, y):
        return np.asarray(x) @ np.asarray(y)

    def whoami():
        return rpc.get_current_worker_info().name

    def boom():
        raise ValueError("boom from callee")

    # all remotely-invoked functions are defined before init_rpc: its
    # barrier guarantees every worker has them before any call arrives
    rpc.init_rpc(f"worker{rank}", rank=rank, world_size=2,
                 master_endpoint=f"127.0.0.1:{port}")

    infos = rpc.get_all_worker_infos()
    assert [i.name for i in infos] == ["worker0", "worker1"], infos

    peer = f"worker{1 - rank}"
    assert rpc.rpc_sync(peer, add, args=(2, 3)) == 5
    fut = rpc.rpc_async(peer, matmul_np,
                        args=(np.eye(4), np.arange(16.).reshape(4, 4)))
    np.testing.assert_allclose(fut.wait(), np.arange(16.).reshape(4, 4))
    assert rpc.rpc_sync(peer, whoami) == peer
    # error propagation
    try:
        rpc.rpc_sync(peer, boom)
    except ValueError as e:
        assert "boom" in str(e)
    else:
        raise AssertionError("exception did not propagate")
    # self-call
    assert rpc.rpc_sync(f"worker{rank}", add, args=(1, 1)) == 2
    rpc.shutdown()
    print("RPC_WORKER_OK", rank)
""")


def _free_port():
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_rpc_two_process():
    port = _free_port()
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    procs = [subprocess.Popen(
        [sys.executable, "-c", _WORKER, str(r), str(port)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env, cwd=os.path.dirname(os.path.dirname(__file__)))
        for r in range(2)]
    try:
        outs = [p.communicate(timeout=180)[0] for p in procs]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.communicate()
    for r, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {r} failed:\n{out}"
        assert f"RPC_WORKER_OK {r}" in out


def test_rpc_requires_init():
    import paddle_tpu.distributed.rpc as rpc
    import pytest
    with pytest.raises(RuntimeError, match="not initialized"):
        rpc.rpc_sync("worker0", lambda: None)
