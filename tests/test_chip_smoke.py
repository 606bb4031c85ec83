"""chip_smoke.py's phases at a tiny size on the CPU — and the first test of
the Pallas row kernel INSIDE the table path: on a one-device mesh with the
scatter gate forced open, ``MatrixServer.add`` (duplicate ids), the device
Add and one fused ``PSTrainer`` transaction run through the interpreted
kernel and must match numpy. Tracing the interpreted kernel (a Python-unrolled
loop of 4 x ROW_GROUP DMA ops) costs seconds per shape at the production group
of 64, so the test runs it at a group of 8 and all three phases share one table
shape and one id bucket. The row kernels' jits are cached by shape, not by
group: 61 x 128 tables appear in no other test."""

import pytest

import multiverso_tpu as mv
from multiverso_tpu import log
from multiverso_tpu.ops import pallas_rows
from multiverso_tpu.parallel import mesh as mesh_lib
from multiverso_tpu.tables import matrix_table

import chip_smoke


def test_smoke_phases_through_the_interpreted_kernel(monkeypatch):
    monkeypatch.setattr(matrix_table, "_use_pallas_scatter",
                        lambda platform, num_shards, *width: num_shards == 1)
    monkeypatch.setattr(pallas_rows, "ROW_GROUP", 8)
    mv.init(mesh_shape="1", **chip_smoke._INIT_FLAGS)
    assert mv.num_servers() == 1  # the first of the 8 virtual devices
    interpreted = (True, True)

    table, checks = chip_smoke.phase_kernels(60, 50, 48, interpreted)
    assert checks["pallas_scatter"] and checks["interpret"] is True
    assert "bare_kernels" in checks and checks["padded_cols"] == 128
    assert checks["row_launches"] == {
        "ROW_LAUNCH_PALLAS_ADD": 2, "ROW_LAUNCH_XLA_ADD": 0,
        "ROW_LAUNCH_PALLAS_GET": 0, "ROW_LAUNCH_XLA_GET": 3}

    # rows of three lane tiles: the same phase on a 300-column table
    _, checks = chip_smoke.phase_kernels(60, 300, 48, interpreted)
    assert checks["pallas_scatter"] and checks["padded_cols"] == 384
    assert "bare_kernels" in checks
    assert checks["row_launches"]["ROW_LAUNCH_PALLAS_ADD"] == 2

    trainer, w_in, checks = chip_smoke.phase_trainer(
        60, 16, 64, 64, 2, 2, interpreted)
    assert checks["untouched_rows_bit_equal"]
    assert trainer.input_table._server_table.plan.interpret is True

    report = chip_smoke.phase_server(trainer.input_table, w_in, 32, 5, 4)
    assert report["backends_initialized"] is False
    assert report["query_ids_equal_numpy"]


def test_the_keyed_phase_through_the_interpreted_lane_kernel():
    """`chip_smoke.phase_keyed` tiny: three keyed FTRL Adds through the
    dispatcher on one CPU device, where the plan takes the lane kernel
    interpreted (it computes the FTRL step on the rows it read: PR 49),
    against XLA's program on a bare state: every entry of the rows named,
    and the count of entries changed anywhere. On the CPU both sides run
    XLA's operations, so `z` is equal in every bit too."""
    mv.init(mesh_shape="1", **chip_smoke._INIT_FLAGS)
    table, checks = chip_smoke.phase_keyed(5000, 600, 3,
                                           expect_kernel=(True, True))
    assert table._server_table.plan.path == "pallas"
    assert checks["pallas_adds"] == 3 and checks["n_entries_differ"] == 0
    assert checks["z_entries_differ"] == 0
    assert checks["changed_outside_the_rows_named"] == [0, 0]
    # 5,000 keys are 40 rows of 128: the dense head names them all
    assert checks["rows_named"] == 40
    mv.shutdown()


def test_four_chip_phase_runs_the_kernel_on_every_shard(monkeypatch):
    """The smoke's four-device pass, tiny: with the gate open the tables
    report the Pallas scatter, both Adds of the kernels phase are counted
    under it (the kernel ran on every shard's block, ids routed to their
    owners), the bare kernels are left to the one-device pass, the rows sit
    in equal parts on four devices, and the trainer's fused transaction
    still engages on the sharded tables."""
    monkeypatch.setattr(matrix_table, "_use_pallas_scatter",
                        lambda platform, num_shards, *width: True)
    monkeypatch.setattr(pallas_rows, "ROW_GROUP", 8)
    mv.init(mesh_shape="4", **chip_smoke._INIT_FLAGS)
    try:
        interpreted = (True, True)
        for cols, lanes in ((50, 128), (300, 384)):
            table, checks = chip_smoke.phase_kernels(62, cols, 48,
                                                     interpreted)
            assert checks["pallas_scatter"] is True
            assert checks["padded_cols"] == lanes
            assert "bare_kernels" not in checks
            assert checks["row_launches"] == {
                "ROW_LAUNCH_PALLAS_ADD": 2, "ROW_LAUNCH_XLA_ADD": 0,
                "ROW_LAUNCH_PALLAS_GET": 0, "ROW_LAUNCH_XLA_GET": 3}
            assert "add" in table._server_table.plan.routed
            shards = chip_smoke.check_shards(table, 4)
            assert len(shards["devices"]) == 4
        trainer, _, checks = chip_smoke.phase_trainer(
            62, 16, 64, 64, 2, 2, interpreted)
        assert checks["untouched_rows_bit_equal"]
        chip_smoke.check_shards(trainer.input_table, 4)
    finally:
        mv.shutdown()


def test_mesh_shape_above_the_device_count_is_fatal():
    with pytest.raises(log.FatalError, match="needs 16 devices, have 8"):
        mesh_lib.build_mesh(shape=(16,))
