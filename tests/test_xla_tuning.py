"""The overlap flag set and its one route to the compiler (tier-1-safe,
no TPU): ``step_compiler_options`` hands the set to a TPU mesh of
several devices and to nothing else, never writes the environment, and
leaves a flag the user set in ``LIBTPU_INIT_ARGS`` to the user."""

import pytest

from horovod_tpu.common import xla_tuning


def test_flag_set_is_what_the_chip_kept():
    """ISSUE 28: asynchronous all-reduce is what the set is for; every
    name once, every value a string libtpu parses."""
    names = [n for n, _ in xla_tuning.TPU_OVERLAP_FLAGS]
    assert len(names) == len(set(names))
    assert "--xla_enable_async_all_reduce" in names
    assert "--xla_tpu_enable_async_collective_fusion_fuse_all_reduce" \
        in names
    for name, value in xla_tuning.TPU_OVERLAP_FLAGS:
        assert name.startswith("--xla_") and "=" not in name
        assert isinstance(value, str) and value


class _Dev:
    def __init__(self, platform):
        self.platform = platform


def _mesh_devices(platform, count):
    import numpy as np

    return np.array([_Dev(platform) for _ in range(count)], dtype=object)


@pytest.mark.parametrize("platform, count, expected", [
    ("cpu", 1, False), ("cpu", 8, False), ("tpu", 1, False),
    ("tpu", 2, True), ("tpu", 4, True), ("gpu", 4, False)])
def test_step_options_only_for_a_tpu_mesh_of_several_devices(
        platform, count, expected):
    opts = xla_tuning.step_compiler_options(
        _mesh_devices(platform, count), env={})
    if not expected:
        assert opts is None
        return
    # compile options carry no leading dashes
    assert opts == {n[2:]: v for n, v in xla_tuning.TPU_OVERLAP_FLAGS}


def test_step_options_leave_user_set_flags_alone():
    name, _ = xla_tuning.TPU_OVERLAP_FLAGS[0]
    env = {"LIBTPU_INIT_ARGS": f"--xla_other=1 {name}=false"}
    opts = xla_tuning.step_compiler_options(_mesh_devices("tpu", 4), env)
    assert name[2:] not in opts
    assert len(opts) == len(xla_tuning.TPU_OVERLAP_FLAGS) - 1
    # the whole set pinned by the user, one flag bare (no value): nothing
    # left to ask for
    pinned = [name] + [f"{n}={v}"
                       for n, v in xla_tuning.TPU_OVERLAP_FLAGS[1:]]
    env = {"LIBTPU_INIT_ARGS": " ".join(pinned)}
    assert xla_tuning.step_compiler_options(
        _mesh_devices("tpu", 4), env) is None
    # and the call itself writes nowhere
    assert env == {"LIBTPU_INIT_ARGS": " ".join(pinned)}


def test_step_options_accept_a_flat_list_of_devices():
    opts = xla_tuning.step_compiler_options(
        [_Dev("tpu"), _Dev("tpu")], env={})
    assert opts is not None


def test_step_options_read_a_mesh_of_any_rank():
    """``spmd_step`` passes ``mesh.devices``, an array of the mesh's own
    shape: a 2x2 TPU mesh is four devices, not two rows."""
    grid = _mesh_devices("tpu", 4).reshape(2, 2)
    assert xla_tuning.step_compiler_options(grid, env={}) == \
        xla_tuning.step_compiler_options(_mesh_devices("tpu", 4), env={})
    # one row of one device is still one chip
    assert xla_tuning.step_compiler_options(
        _mesh_devices("tpu", 1).reshape(1, 1), env={}) is None


def test_step_options_for_the_devices_of_a_real_cpu_mesh():
    import jax
    import numpy as np
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()).reshape(2, -1), ("cross", "local"))
    assert mesh.devices.size > 1
    assert xla_tuning.step_compiler_options(mesh.devices, env={}) is None
