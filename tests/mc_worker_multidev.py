"""Multi-controller worker with >1 device per process (run under
`hvdrun -np 2 --devices-per-proc 2`): ranks are processes, devices are
an implementation detail — allreduce must not double-count."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import horovod_tpu as hvd


def main():
    hvd.init()
    r, n = hvd.process_rank(), hvd.num_processes()
    assert n == 2 and hvd.size() == 4, (n, hvd.size())

    x = np.full((4,), float(r + 1), np.float32)
    out = np.asarray(hvd.allreduce(x, average=False))
    np.testing.assert_allclose(out, 3.0)  # 1 + 2, not 2*(1+2)
    out = np.asarray(hvd.allreduce(x, average=True))
    np.testing.assert_allclose(out, 1.5)

    # Ragged (size % k != 0) and integer paths through the chunked
    # kernel: 5 elements over k=2 local devices pad to chunks of 3.
    xi = np.arange(5, dtype=np.int32) + r
    np.testing.assert_array_equal(
        np.asarray(hvd.allreduce(xi, average=False)),
        2 * np.arange(5) + 1)

    # Counted-bytes check: the cross-process
    # all-reduce must move chunk = n/k elements in k parallel groups
    # of nproc ranks — the k-fold payload duplication is gone.
    import re

    from horovod_tpu.ops import eager
    from horovod_tpu.runtime import state as _state
    st = _state.check_initialized()
    key = ("mc_allreduce2", False, (4,), "float32")
    assert key in st.op_cache, sorted(st.op_cache)
    mesh2 = eager._mc_mesh2(st)
    garr, chunk = eager._mc_chunked_global(
        st, mesh2, np.ones((4,), np.float32))
    assert chunk == 2, chunk
    hlo = st.op_cache[key].lower(garr).compile().as_text()
    ars = [l for l in hlo.splitlines() if "all-reduce(" in l]
    assert len(ars) == 1, ars
    line = ars[0]
    assert "f32[1,1,2]" in line, line          # chunk, not the block
    m = re.search(r"replica_groups=\{(.*?)\}\}", line)
    assert m, line  # HLO text format changed — update the check
    groups = re.findall(r"\{([\d,]+)\}", m.group(0))
    assert len(groups) == 2, line              # k chunk groups...
    assert all(len(g.split(",")) == 2 for g in groups), line  # of nproc

    got = np.asarray(hvd.broadcast(
        np.full((2,), float(r * 5), np.float32), 1))
    np.testing.assert_allclose(got, 5.0)

    # reducescatter with k=2 local devices: the psum_scatter path
    # (dim0 % size == 0) must correct the k-fold duplication exactly.
    x = np.arange(8, dtype=np.float32) + r  # sum: 2*arange+1
    np.testing.assert_allclose(
        np.asarray(hvd.reducescatter(x)),
        (2 * np.arange(8) + 1)[r * 4:(r + 1) * 4])
    # dim0 % nproc == 0 but % size != 0: the psum+slice fallback.
    x = np.arange(6, dtype=np.float32) + r
    np.testing.assert_allclose(
        np.asarray(hvd.reducescatter(x)),
        (2 * np.arange(6) + 1)[r * 3:(r + 1) * 3])
    # integer exactness through both paths
    np.testing.assert_array_equal(
        np.asarray(hvd.reducescatter(np.arange(4, dtype=np.int32) + r)),
        (2 * np.arange(4) + 1)[r * 2:(r + 1) * 2])

    # alltoall with k=2 local devices: k parallel one-device-per-
    # process exchange groups, every local device holds the result.
    x = np.arange(4, dtype=np.float32) + 10 * r
    exp = (np.array([0, 1, 10, 11], np.float32) if r == 0
           else np.array([2, 3, 12, 13], np.float32))
    np.testing.assert_allclose(np.asarray(hvd.alltoall(x)), exp)

    gathered = np.asarray(hvd.allgather(
        np.full((r + 1, 2), float(r), np.float32)))
    assert gathered.shape == (3, 2), gathered.shape

    try:
        hvd.broadcast(np.zeros(2, np.float32), 3)  # valid device slot,
        raise AssertionError("expected ValueError")  # invalid process
    except ValueError:
        pass

    # Object/grouped APIs under multi-device ownership (k-duplication
    # corrections must count processes, not devices).
    objs = hvd.allgather_object({"r": r})
    assert [o["r"] for o in objs] == [0, 1], objs
    g = hvd.grouped_allreduce(
        [np.full((2,), float(r + 1), np.float32),
         np.full((3,), 2.0 * r, np.float32)], average=False)
    np.testing.assert_allclose(np.asarray(g[0]), 3.0)  # 1+2
    np.testing.assert_allclose(np.asarray(g[1]), 2.0)  # 0+2

    print(f"MCMD_OK rank={r}")


if __name__ == "__main__":
    main()
