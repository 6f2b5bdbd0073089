"""build_mesh: how many of the devices a mesh really spans, and that it
says so when that is fewer than it was given."""
import warnings

import jax
import pytest

from paddle_tpu.distributed.mesh import build_mesh


@pytest.mark.parametrize("degrees,spans", [
    ({}, 8),                                # no degrees: dp over all
    ({"sharding": 2, "tp": 4}, 8),
    ({"dp": 2, "pp": 2, "sep": 2}, 8),
])
def test_a_mesh_over_every_device_is_silent(degrees, spans):
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        mesh = build_mesh(**degrees)
    assert mesh.devices.size == spans == len(jax.devices())


@pytest.mark.parametrize("degrees,spans", [({"tp": 2}, 2),
                                           ({"sharding": 2, "tp": 2}, 4)])
def test_a_mesh_over_part_of_the_devices_says_so(degrees, spans):
    with pytest.warns(UserWarning, match=f"first {spans} of 8 devices"):
        mesh = build_mesh(**degrees)
    assert list(mesh.devices.flat) == jax.devices()[:spans]


def test_named_devices_are_the_whole_mesh():
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        one = build_mesh(devices=jax.devices()[3:4])
        four = build_mesh(sharding=2, tp=2, devices=jax.devices()[4:])
    assert list(one.devices.flat) == jax.devices()[3:4]
    assert list(four.devices.flat) == jax.devices()[4:]


def test_more_degrees_than_devices_raise():
    with pytest.raises(ValueError, match="> 8 devices"):
        build_mesh(dp=4, tp=4)
