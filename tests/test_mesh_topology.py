"""Topology-aware mesh placement (reference ``utils/groups.py:544`` /
``runtime/pipe/topology.py:12`` rank-mapping parity; SURVEY §5.8).

Mocked multi-chip topologies (the same attribute surface
``jax._src.mesh_utils`` reads: platform/device_kind/coords/core_on_chip/
slice_index/process_index) verify that on TPU the 'tensor' axis lands on
nearest-neighbor ICI and that multi-slice meshes put only 'data' on DCN,
while the CPU path keeps the deterministic device-order reshape every other
test depends on.
"""

import numpy as np
import pytest

import jax

from deepspeed_tpu.comm.mesh import MESH_AXES, MeshManager, _arrange_devices


class MockTpu:
    platform = "tpu"

    def __init__(self, id, coords, device_kind="TPU v5p", core_on_chip=0,
                 slice_index=0, process_index=0):
        self.id = id
        self.coords = coords
        self.device_kind = device_kind
        self.core_on_chip = core_on_chip
        self.slice_index = slice_index
        self.process_index = process_index

    def __repr__(self):
        return f"MockTpu(id={self.id}, xyz={self.coords}, s={self.slice_index})"


def v5p_cuboid(nx, ny, nz, slice_index=0, id0=0):
    """Devices in process-tiled (z, y, x) order — the jax.devices() order
    whose naive reshape puts logical neighbors on different hosts."""
    devs = []
    i = id0
    for z in range(nz):
        for y in range(ny):
            for x in range(nx):
                devs.append(MockTpu(i, (x, y, z), slice_index=slice_index))
                i += 1
    return devs


def sizes_for(**axes):
    return [axes.get(a, 1) for a in MESH_AXES]


def is_subtorus(group, dims):
    """True iff the group's chips form a compact contiguous sub-torus: along
    each physical dim the used coordinates are a contiguous run (mod wrap)
    and the runs' extents multiply to the group size (no strides, no holes).
    A collective over such a group rides only local ICI links — this is the
    property that makes TP 'nearest-neighbor', whether the logical axis maps
    to one physical axis or a composite of them."""
    coords = [d.coords for d in group]
    extent = 1
    for i, dim in enumerate(dims):
        used = sorted({c[i] for c in coords})
        extent *= len(used)
        runs_contig = all(b - a == 1 for a, b in zip(used[:-1], used[1:]))
        wraps = (used[0] == 0 and used[-1] == dim - 1 and
                 len(used) < dim)  # e.g. {3,0} on a ring of 4
        if not runs_contig and not wraps:
            return False
    return extent == len(group)


def test_tensor_axis_rides_ici():
    dims = (4, 2, 2)
    devs = v5p_cuboid(*dims)
    arr, dcn = _arrange_devices(devs, sizes_for(data=4, tensor=4))
    assert arr.shape == tuple(sizes_for(data=4, tensor=4))
    assert dcn is None  # single slice: every axis rides ICI
    assert {d.id for d in arr.flat} == set(range(16))
    grid = arr.reshape(4, 4)  # collapse the size-1 axes
    for ring in grid:  # each TP group is a compact sub-torus
        assert is_subtorus(ring, dims), f"tensor group spread out: {list(ring)}"
    for col in grid.T:  # so is each DP group
        assert is_subtorus(col, dims), f"data group spread out: {list(col)}"


def test_naive_reshape_would_stride_the_torus():
    # a hostile-but-legal device order (even-x chips enumerated before odd-x,
    # as process tiling over a twisted pod can produce): the plain reshape
    # yields strided TP groups; documents that _arrange_devices load-bears
    dims = (4, 2, 2)
    devs = sorted(v5p_cuboid(*dims), key=lambda d: (d.coords[0] % 2, d.id))
    naive = np.asarray(devs).reshape(sizes_for(data=4, tensor=4)).reshape(4, 4)
    assert any(not is_subtorus(ring, dims) for ring in naive), \
        "mock order unexpectedly benign — strengthen the mock"
    arr, _ = _arrange_devices(devs, sizes_for(data=4, tensor=4))
    for ring in arr.reshape(4, 4):
        assert is_subtorus(ring, dims)


def test_multislice_puts_data_on_dcn():
    # two v5e 2x2 slices; 'data' must span slices, 'tensor' must not
    devs = (v5p_cuboid(2, 2, 1, slice_index=0, id0=0)
            + v5p_cuboid(2, 2, 1, slice_index=1, id0=4))
    for d in devs:
        d.device_kind = "TPU v5e"
    arr, dcn = _arrange_devices(devs, sizes_for(data=2, tensor=4))
    assert dcn == "data"  # feeds MeshManager.dcn_axes / link-class tagging
    assert {d.id for d in arr.flat} == set(range(8))
    grid = arr.reshape(2, 4)
    for row in grid:  # a tensor ring stays inside one slice (ICI)
        assert len({d.slice_index for d in row}) == 1
    for col in grid.T:  # the data axis is the DCN axis
        assert {d.slice_index for d in col} == {0, 1}


def test_multislice_no_divisible_axis_raises():
    devs = [MockTpu(i, (i % 2, 0, 0), device_kind="TPU v5e",
                    slice_index=i // 2)
            for i in range(8)]  # 4 slices of 2
    with pytest.raises(ValueError, match="slice count"):
        _arrange_devices(devs, sizes_for(data=2, seq=2, tensor=2))


def test_cpu_mesh_order_unchanged():
    devs = jax.devices()
    arr, dcn = _arrange_devices(devs, sizes_for(data=4, tensor=2))
    assert list(arr.flat) == list(devs) and dcn is None
    mm = MeshManager.create({"data": 4, "tensor": 2})
    assert mm.tp_world_size == 2 and mm.dp_world_size == 4


def test_unknown_topology_raises():
    # holes in the cuboid make mesh_utils raise; a device-order reshape in
    # its place would be a wrong mesh that still runs, so the error stands
    devs = v5p_cuboid(4, 2, 2)[:8] + v5p_cuboid(4, 2, 2)[8:]
    devs[3].coords = (17, 9, 5)  # break the cuboid
    with pytest.raises(AssertionError):
        _arrange_devices(devs, sizes_for(data=4, tensor=4))
