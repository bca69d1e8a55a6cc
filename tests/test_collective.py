"""Collective layer tests.

Reference test pattern: ``python/ray/util/collective/tests/`` — CPU (gloo)
tests standing in for the device backend (SURVEY.md §4).  The shm backend
runs among real actor processes; the xla backend runs on the 8-virtual-
device CPU mesh.
"""

import numpy as np
import pytest

import ray_tpu
from ray_tpu.util import collective as col
from ray_tpu.util.collective.types import ReduceOp


@ray_tpu.remote
class Rank:
    def __init__(self, rank, world, group="default"):
        col.init_collective_group(world, rank, "shm", group)
        self.rank = rank
        self.world = world
        self.group = group

    def allreduce(self, x):
        return col.allreduce(np.asarray(x, np.float32), self.group)

    def allreduce_op(self, x, op):
        from ray_tpu.util.collective.types import ReduceOp
        ops = {"max": ReduceOp.MAX, "min": ReduceOp.MIN,
               "sum": ReduceOp.SUM}
        return col.allreduce(np.asarray(x, np.float32), self.group,
                             op=ops[op])

    def allgather(self, x):
        return col.allgather(np.asarray(x, np.float32), self.group)

    def broadcast(self, x):
        return col.broadcast(np.asarray(x, np.float32), 0, self.group)

    def reducescatter(self, xs):
        return col.reducescatter([np.asarray(x, np.float32) for x in xs],
                                 self.group)

    def alltoall(self, xs):
        return col.alltoall([np.asarray(x, np.float32) for x in xs],
                            self.group)

    def reduce_to0(self, x):
        return col.reduce(np.asarray(x, np.float32), 0, self.group)

    def barrier_then(self, x):
        col.barrier(self.group)
        return x

    def sendrecv(self, peer, x):
        if self.rank == 0:
            col.send(np.asarray(x, np.float32), peer, self.group)
            return None
        return col.recv(peer, self.group)

    def rank_info(self):
        return (col.get_rank(self.group),
                col.get_collective_group_size(self.group))


def _mk_group(n, group="default"):
    actors = [Rank.options(num_cpus=0.5).remote(r, n, group)
              for r in range(n)]
    ray_tpu.get([a.__ray_ready__.remote() for a in actors])
    return actors


class TestShmBackend:
    def test_allreduce(self, ray_start_regular):
        actors = _mk_group(4)
        outs = ray_tpu.get([a.allreduce.remote([float(i)] * 3)
                            for i, a in enumerate(actors)])
        for o in outs:
            np.testing.assert_allclose(o, [6.0, 6.0, 6.0])

    def test_allreduce_large_tensor(self, ray_start_regular):
        # > INLINE_LIMIT → object-store path
        actors = _mk_group(2)
        big = np.ones(100_000, np.float32)
        outs = ray_tpu.get([a.allreduce.remote(big) for a in actors])
        for o in outs:
            np.testing.assert_allclose(o, 2 * big)

    def test_allreduce_ring_path(self, ray_start_regular):
        """≥ RING_THRESHOLD with world > 2 → the chunked ring algorithm
        (reduce-scatter + all-gather over p2p hops); numerics must match
        the naive path exactly for SUM of integers-as-floats."""
        actors = _mk_group(3)
        n = (4 * 1024 * 1024) // 4 + 7  # just over the ring threshold
        big = np.arange(n, dtype=np.float32) % 97
        outs = ray_tpu.get([a.allreduce.remote(big) for a in actors],
                           timeout=300)
        for o in outs:
            np.testing.assert_allclose(o, 3 * big)

    def test_allreduce_ring_max_op(self, ray_start_regular):
        actors = _mk_group(3)
        n = (4 * 1024 * 1024) // 4
        outs = ray_tpu.get(
            [a.allreduce_op.remote(np.full(n, float(i), np.float32), "max")
             for i, a in enumerate(actors)], timeout=300)
        for o in outs:
            np.testing.assert_allclose(o, np.full(n, 2.0))

    def test_allgather_ordering(self, ray_start_regular):
        actors = _mk_group(3)
        outs = ray_tpu.get([a.allgather.remote([float(i)])
                            for i, a in enumerate(actors)])
        for o in outs:
            assert [float(x[0]) for x in o] == [0.0, 1.0, 2.0]

    def test_broadcast(self, ray_start_regular):
        actors = _mk_group(3)
        outs = ray_tpu.get([a.broadcast.remote([float(i + 1)])
                            for i, a in enumerate(actors)])
        for o in outs:
            np.testing.assert_allclose(o, [1.0])  # rank 0's value

    def test_reducescatter(self, ray_start_regular):
        n = 2
        actors = _mk_group(n)
        # each rank contributes [its rank+1] * n chunks of value rank+1
        outs = ray_tpu.get([
            a.reducescatter.remote([[float(r + 1)], [float(r + 1)]])
            for r, a in enumerate(actors)])
        # chunk j = sum over ranks of (rank+1) = 3
        for o in outs:
            np.testing.assert_allclose(o, [3.0])

    def test_alltoall(self, ray_start_regular):
        n = 2
        actors = _mk_group(n)
        outs = ray_tpu.get([
            a.alltoall.remote([[float(10 * r + 0)], [float(10 * r + 1)]])
            for r, a in enumerate(actors)])
        # rank i receives [rank0's chunk i, rank1's chunk i]
        np.testing.assert_allclose([float(x[0]) for x in outs[0]], [0., 10.])
        np.testing.assert_allclose([float(x[0]) for x in outs[1]], [1., 11.])

    def test_reduce_dst_only(self, ray_start_regular):
        actors = _mk_group(2)
        outs = ray_tpu.get([a.reduce_to0.remote([1.0]) for a in actors])
        np.testing.assert_allclose(outs[0], [2.0])

    def test_sendrecv(self, ray_start_regular):
        actors = _mk_group(2)
        r0 = actors[0].sendrecv.remote(1, [7.0, 8.0])
        r1 = actors[1].sendrecv.remote(0, None)
        assert ray_tpu.get(r0) is None
        np.testing.assert_allclose(ray_tpu.get(r1), [7.0, 8.0])

    def test_p2p_object_outlives_a_slow_receiver(self, ray_start_regular,
                                                 monkeypatch):
        """A receiver that has seen a send's key but not yet read its
        object (descheduled under load) must still find the object after
        the sender's NEXT send, which unpins every send whose key is gone:
        recv reads first and deletes the key after.  Deleting first was
        the ring tests' load-sensitive hang (the get waited for a freed
        object).  Both ranks live in this process, so the race is played
        step by step."""
        from ray_tpu.util.collective.collective_group.shm_group import (
            ShmCollectiveGroup)
        g0 = ShmCollectiveGroup(2, 0, "slow_recv")
        g1 = ShmCollectiveGroup(2, 1, "slow_recv")
        first = np.arange(300_000, dtype=np.float32)        # over INLINE_LIMIT
        second = first + 1
        g0.send(first, 1)
        fetch = g1._fetch

        def slow_fetch(blob, timeout=None):
            # between the receiver's look at the key and its read of the
            # object: the sender's next send, and its releases flushed
            g0.send(second, 1)
            g0._w._flush_releases()
            return fetch(blob, 20.0)

        monkeypatch.setattr(g1, "_fetch", slow_fetch)
        np.testing.assert_array_equal(g1.recv(0, timeout=20.0), first)
        monkeypatch.setattr(g1, "_fetch", fetch)
        np.testing.assert_array_equal(g1.recv(0, timeout=20.0), second)

    def test_rank_introspection(self, ray_start_regular):
        actors = _mk_group(2)
        infos = ray_tpu.get([a.rank_info.remote() for a in actors])
        assert infos == [(0, 2), (1, 2)]

    def test_uninitialized_rank_is_minus1(self, ray_start_regular):
        assert col.get_rank("nope") == -1
        assert col.get_collective_group_size("nope") == -1

    def test_sequence_of_ops(self, ray_start_regular):
        # multiple collectives in order exercises seq cleanup
        actors = _mk_group(2)
        for k in range(5):
            outs = ray_tpu.get([a.allreduce.remote([float(k)])
                                for a in actors])
            for o in outs:
                np.testing.assert_allclose(o, [2.0 * k])


class TestXlaBackend:
    def test_allreduce(self, ray_start_regular):
        g = col.xla_group()
        n = g.world_size
        x = np.arange(n * 4, dtype=np.float32).reshape(n, 4)
        out = np.asarray(g.allreduce(x))
        expect = x.sum(0)
        for i in range(n):
            np.testing.assert_allclose(out[i], expect)

    def test_allreduce_max(self, ray_start_regular):
        g = col.xla_group()
        n = g.world_size
        x = np.arange(n, dtype=np.float32)[:, None]
        out = np.asarray(g.allreduce(x, ReduceOp.MAX))
        np.testing.assert_allclose(out, np.full((n, 1), n - 1.0))

    def test_allgather(self, ray_start_regular):
        g = col.xla_group()
        n = g.world_size
        x = np.arange(n, dtype=np.float32)[:, None]
        out = np.asarray(g.allgather(x))
        assert out.shape == (n, n, 1)
        for i in range(n):
            np.testing.assert_allclose(out[i, :, 0], np.arange(n))

    def test_reducescatter(self, ray_start_regular):
        g = col.xla_group()
        n = g.world_size
        # device i contributes row vector of ones → chunk j sums to n
        x = np.ones((n, n, 2), np.float32)
        out = np.asarray(g.reducescatter(x))
        np.testing.assert_allclose(out, np.full((n, 2), float(n)))

    def test_alltoall_transpose(self, ray_start_regular):
        g = col.xla_group()
        n = g.world_size
        x = np.arange(n * n, dtype=np.float32).reshape(n, n, 1)
        out = np.asarray(g.alltoall(x))
        np.testing.assert_allclose(out[..., 0], x[..., 0].T)

    def test_barrier(self, ray_start_regular):
        col.xla_group().barrier()
