"""The node-sharded served path with full-gate's statics
(`benchmark/configs/fullgate-10k.json`): on meshes of 2, 4 and 8
devices, on the single program and on the ladder's chunked rung, the
service places bit-identically to one device (tests/service_mesh.py)."""

import pytest

import service_mesh

CONFIG = "full-gate"


@pytest.fixture(scope="module", params=["program", "chunked"],
                ids=lambda rung: f"{CONFIG}-{rung}")
def rung(request):
    chunked = request.param == "chunked"
    return chunked, service_mesh.one_device_run(CONFIG, chunked)


@pytest.mark.parametrize("devices", [2, 4, 8], ids=lambda d: f"{d}dev")
def test_service_sharded_conformance(rung, devices):
    chunked, reference = rung
    service_mesh.check_sharded_conformance(CONFIG, chunked, devices,
                                           reference)
