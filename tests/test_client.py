"""ray:// client mode (reference: Ray Client, python/ray/util/client/ and
ray_client.proto): the client process attaches through the client server
without joining the cluster."""

import os
import subprocess
import sys
import textwrap

import ray_tpu

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CLIENT_SCRIPT = textwrap.dedent(
    """
    import sys
    sys.path.insert(0, %(repo)r)
    import ray_tpu

    ray_tpu.init(address="ray://127.0.0.1:%(port)d")

    # objects
    ref = ray_tpu.put({"k": [1, 2, 3]})
    assert ray_tpu.get(ref) == {"k": [1, 2, 3]}

    # tasks (with a by-reference arg)
    @ray_tpu.remote
    def add(a, b):
        return a + b

    big = ray_tpu.put(40)
    out = ray_tpu.get(add.remote(big, 2), timeout=60)
    assert out == 42, out

    # ready/not-ready split
    ready, not_ready = ray_tpu.wait([ref], num_returns=1, timeout=10)
    assert len(ready) == 1 and not not_ready

    # actors
    @ray_tpu.remote
    class Counter:
        def __init__(self):
            self.n = 0

        def incr(self, k=1):
            self.n += k
            return self.n

    c = Counter.remote()
    assert ray_tpu.get(c.incr.remote(), timeout=60) == 1
    assert ray_tpu.get(c.incr.remote(5), timeout=60) == 6
    ray_tpu.kill(c)

    # cluster introspection goes through the proxy
    nodes = ray_tpu.nodes()
    assert len(nodes) == 1 and nodes[0]["Alive"]
    res = ray_tpu.cluster_resources()
    assert res.get("CPU", 0) >= 1

    # streaming generators proxy stream reads through the client server
    # (tasks and actor methods; items pin server-side for this session)
    @ray_tpu.remote(num_returns="streaming")
    def gen(n):
        for i in range(n):
            yield i * 10

    assert [ray_tpu.get(r, timeout=60) for r in gen.remote(4)] == [0, 10, 20, 30]

    @ray_tpu.remote
    class Gen:
        def squares(self, n):
            for i in range(n):
                yield i * i

    gactor = Gen.remote()
    g = gactor.squares.options(num_returns="streaming").remote(4)
    assert [ray_tpu.get(r, timeout=60) for r in g] == [0, 1, 4, 9]

    ray_tpu.shutdown()
    print("CLIENT_OK")
    """
)


def test_client_mode_end_to_end(shutdown_only):
    node = ray_tpu.init(
        num_cpus=4, _system_config={"client_server_port": 0}
    )
    assert node.client_server is not None
    port = node.client_server.address[1]
    script = CLIENT_SCRIPT % {"repo": REPO, "port": port}
    proc = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True, text=True, timeout=180,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    assert "CLIENT_OK" in proc.stdout


def test_client_server_survives_client_exit(shutdown_only):
    """A second client can attach after the first disconnects."""
    node = ray_tpu.init(
        num_cpus=4, _system_config={"client_server_port": 0}
    )
    port = node.client_server.address[1]
    quick = textwrap.dedent(
        """
        import sys
        sys.path.insert(0, %(repo)r)
        import ray_tpu
        ray_tpu.init(address="ray://127.0.0.1:%(port)d")
        assert ray_tpu.get(ray_tpu.put(11)) == 11
        ray_tpu.shutdown()
        print("OK")
        """
    ) % {"repo": REPO, "port": port}
    for _ in range(2):
        proc = subprocess.run(
            [sys.executable, "-c", quick],
            capture_output=True, text=True, timeout=120,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        assert proc.returncode == 0, (proc.stdout, proc.stderr)
        assert "OK" in proc.stdout


VALUES_SCRIPT = textwrap.dedent(
    """
    import sys
    sys.path.insert(0, %(repo)r)
    import numpy as np
    import ray_tpu
    from ray_tpu.object_ref import unpack_stream_value
    from ray_tpu.serve.handle import DeploymentResponseGenerator

    ray_tpu.init(address="ray://127.0.0.1:%(port)d")

    @ray_tpu.remote(num_returns="streaming")
    def gen(n):
        for i in range(n):
            yield {"i": i}

    # the handle's reader has one path, whatever kind of worker it runs on
    assert list(DeploymentResponseGenerator(gen.remote(4))) == [
        {"i": i} for i in range(4)
    ]

    @ray_tpu.remote
    class Gen:
        def mixed(self):
            yield "small"
            yield np.full((300_000,), 7, np.float32)  # through plasma
            raise RuntimeError("stream broke")

    a = Gen.remote()
    g = a.mixed.options(num_returns="streaming").remote()
    got = []
    try:
        while True:
            got.extend(unpack_stream_value(v) for v in g.take_values(60.0))
    except Exception as e:
        assert "stream broke" in str(e), e
    assert got[0] == "small" and got[1].shape == (300_000,) and got[1][0] == 7.0
    assert g.take_values(60.0) is None

    ray_tpu.shutdown()
    print("CLIENT_OK")
    """
)


def test_client_mode_streams_values(shutdown_only):
    """The proxied worker answers the value-reading operation beside
    next_stream_item: the items travel packed, the server's worker counts
    them, and nothing is made a ref or pinned for the session."""
    node = ray_tpu.init(
        num_cpus=4, _system_config={"client_server_port": 0}
    )
    server = node.client_server
    port = server.address[1]
    proc = subprocess.run(
        [sys.executable, "-c", VALUES_SCRIPT % {"repo": REPO, "port": port}],
        capture_output=True, text=True, timeout=180,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    assert "CLIENT_OK" in proc.stdout
    counts = server.worker.stream_counts
    assert counts["values"] == 6 and counts["refs"] == 0
    assert 2 <= counts["takes"] <= 6
    assert not server.worker._streams
