"""Launcher auto-configuration (run.py) and accelerator sniffing.

Reference analog: ElasticLaunchConfig.auto_configure_params
(dlrover/python/elastic_agent/torch/training.py:143-157) — node count
from env, device count as the nproc-per-node analog, auto network check
at >=4 nodes. TPU twist under test: the device count must come from
kernel device nodes, never from initializing JAX in the launcher/agent
process (libtpu is exclusive-access).
"""

import os

import pytest

from dlrover_tpu.common.accelerator import sniff_accelerator
from dlrover_tpu.common.constants import EnvKey
from dlrover_tpu.run import auto_configure, parse_args


def _args(*argv):
    return parse_args([*argv, "train.py"])


_KEYS = (EnvKey.NODE_NUM, EnvKey.ACCELERATOR,
         EnvKey.DEVICE_COUNT_OVERRIDE, EnvKey.INIT_TIMEOUT)


@pytest.fixture
def clean_env(monkeypatch):
    for key in _KEYS:
        monkeypatch.delenv(key, raising=False)
    yield monkeypatch
    # auto_configure writes os.environ directly; monkeypatch only
    # restores keys that existed before, so scrub the rest explicitly
    for key in _KEYS:
        os.environ.pop(key, None)


def _pci_dev(root, addr, vendor, pci_class):
    d = root / addr
    d.mkdir(parents=True)
    (d / "vendor").write_text(vendor + "\n")
    (d / "class").write_text(pci_class + "\n")


class TestSniffAccelerator:
    def test_accel_nodes_counted(self, tmp_path):
        for i in range(4):
            (tmp_path / f"accel{i}").touch()
        assert sniff_accelerator(str(tmp_path), str(tmp_path / "pci")) \
            == ("tpu", 4)

    def test_unreadable_sysfs_link_warns(self, tmp_path):
        """A /dev/accel node whose sysfs PCI link is unreadable falls
        back to the megacore default — with a warning naming the escape
        hatch, so a v2/v3 undercount is diagnosable from the log."""
        import logging

        records: list[logging.LogRecord] = []

        class _Capture(logging.Handler):
            def emit(self, record):
                records.append(record)

        # the repo's loggers set propagate=False, so capture directly
        logger = logging.getLogger("dlrover_tpu.common.accelerator")
        handler = _Capture(level=logging.WARNING)
        logger.addHandler(handler)
        try:
            (tmp_path / "accel0").touch()
            kind, count = sniff_accelerator(
                str(tmp_path), str(tmp_path / "pci"),
                str(tmp_path / "accel_class"),
            )
            assert (kind, count) == ("tpu", 1)
            assert any("DLROVER_TPU_DEVICE_COUNT" in r.getMessage()
                       for r in records)
            # a READABLE link stays quiet
            records.clear()
            d = tmp_path / "accel_class" / "accel0" / "device"
            d.mkdir(parents=True)
            (d / "device").write_text("0x005e\n")
            assert sniff_accelerator(
                str(tmp_path), str(tmp_path / "pci"),
                str(tmp_path / "accel_class"),
            ) == ("tpu", 1)
            assert not records
        finally:
            logger.removeHandler(handler)

    def test_sysfs_google_accelerators_counted(self, tmp_path):
        pci = tmp_path / "pci"
        _pci_dev(pci, "0000:00:01.0", "0x1ae0", "0x120000")
        _pci_dev(pci, "0000:00:02.0", "0x1ae0", "0x120000")
        # gVNIC shares Google's vendor id but is class 0x0200 (NIC):
        # it must NOT count as a chip
        _pci_dev(pci, "0000:00:03.0", "0x1ae0", "0x020000")
        # someone else's VFIO-bound accelerator must not count either
        _pci_dev(pci, "0000:00:04.0", "0x10de", "0x120000")
        assert sniff_accelerator(str(tmp_path), str(pci)) == ("tpu", 2)

    def test_v3_chips_count_two_cores_each(self, tmp_path):
        """TPU v2/v3 chips (PCI ids 0x0027/0x0037) carry two
        TensorCores — the count must use JAX-device semantics (4 chips
        -> 8 devices on a v3-8 host), matching jax.local_device_count."""
        accel_cls = tmp_path / "accel_class"
        for i in range(4):
            (tmp_path / f"accel{i}").touch()
            d = accel_cls / f"accel{i}" / "device"
            d.mkdir(parents=True)
            (d / "device").write_text("0x0037\n")
        assert sniff_accelerator(
            str(tmp_path), str(tmp_path / "pci"), str(accel_cls)
        ) == ("tpu", 8)

    def test_v4_chips_count_one_device_each(self, tmp_path):
        accel_cls = tmp_path / "accel_class"
        for i in range(4):
            (tmp_path / f"accel{i}").touch()
            d = accel_cls / f"accel{i}" / "device"
            d.mkdir(parents=True)
            (d / "device").write_text("0x005e\n")
        assert sniff_accelerator(
            str(tmp_path), str(tmp_path / "pci"), str(accel_cls)
        ) == ("tpu", 4)

    def test_bare_host_is_cpu(self, tmp_path):
        pci = tmp_path / "pci"
        _pci_dev(pci, "0000:00:03.0", "0x1ae0", "0x020000")  # gVNIC only
        assert sniff_accelerator(str(tmp_path), str(pci)) == ("cpu", 1)

    def test_vfio_bound_chip_counts_groups_this_process_can_open(
            self, tmp_path):
        """The v5e machine of PR 22: no /dev/accel*, four Google
        functions of an unassigned PCI class in sysfs, ONE /dev/vfio
        group node — the VM holds one chip, and one is the answer."""
        pci = tmp_path / "pci"
        iommu = tmp_path / "iommu"
        for i, addr in enumerate(("08", "09", "0a", "0b")):
            _pci_dev(pci, f"0000:00:{addr}.0", "0x1ae0", "0xff0000")
            (pci / f"0000:00:{addr}.0" / "device").write_text("0x0063\n")
            grp = iommu / str(i) / "devices" / f"0000:00:{addr}.0"
            grp.mkdir(parents=True)
            (grp / "vendor").write_text("0x1ae0\n")
            (grp / "device").write_text("0x0063\n")
        (tmp_path / "vfio").mkdir()
        (tmp_path / "vfio" / "vfio").touch()  # the container, not a chip
        (tmp_path / "vfio" / "3").touch()
        assert sniff_accelerator(
            str(tmp_path), str(pci), str(tmp_path / "cls"), str(iommu)
        ) == ("tpu", 1)

    def test_unassigned_class_v5e_functions_found_by_device_id(
            self, tmp_path):
        pci = tmp_path / "pci"
        for addr in ("08", "09"):
            _pci_dev(pci, f"0000:00:{addr}.0", "0x1ae0", "0xff0000")
            (pci / f"0000:00:{addr}.0" / "device").write_text("0x0063\n")
        assert sniff_accelerator(
            str(tmp_path), str(pci), str(tmp_path / "cls"),
            str(tmp_path / "iommu")) == ("tpu", 2)


class TestControlPlaneStaysOffJax:
    """A chip belongs to one process: the launcher and the agent must
    leave it to the trainer they spawn."""

    def test_detect_local_devices_never_imports_jax_in_the_agent(
            self, monkeypatch, tmp_path):
        """Empty /dev and sysfs (a sealed VM may show neither): the
        count comes from a short-lived child, not from this process."""
        import subprocess
        import sys

        from dlrover_tpu.agent import elastic_agent

        monkeypatch.delenv(EnvKey.DEVICE_COUNT_OVERRIDE, raising=False)
        monkeypatch.setattr(elastic_agent, "sniff_accelerator",
                            lambda: ("cpu", 1))
        seen = []
        real_run = subprocess.run

        def spy(cmd, **kw):
            seen.append(cmd)
            return real_run(cmd, **kw)

        monkeypatch.setattr(elastic_agent.subprocess, "run", spy)
        jax_mod = sys.modules.pop("jax")  # the test process has it
        try:
            count = elastic_agent._detect_local_devices()
            assert "jax" not in sys.modules
        finally:
            sys.modules["jax"] = jax_mod
        assert count == 8  # the child inherits the 8-device CPU mesh
        assert len(seen) == 1 and "import jax" in seen[0][-1]

    def test_detect_local_devices_prefers_override_and_sniff(
            self, monkeypatch):
        from dlrover_tpu.agent import elastic_agent

        monkeypatch.setattr(elastic_agent.subprocess, "run",
                            lambda *a, **k: pytest.fail("child spawned"))
        monkeypatch.setattr(elastic_agent, "sniff_accelerator",
                            lambda: ("tpu", 4))
        monkeypatch.delenv(EnvKey.DEVICE_COUNT_OVERRIDE, raising=False)
        assert elastic_agent._detect_local_devices() == 4
        monkeypatch.setenv(EnvKey.DEVICE_COUNT_OVERRIDE, "2")
        assert elastic_agent._detect_local_devices() == 2

    @pytest.mark.parametrize("module", [
        "dlrover_tpu.run", "dlrover_tpu.agent.elastic_agent",
        "dlrover_tpu.master.job_master", "dlrover_tpu.agent.standby",
    ])
    def test_importing_the_control_plane_does_not_import_jax(self, module):
        import subprocess
        import sys

        out = subprocess.run(
            [sys.executable, "-c",
             f"import sys, {module}; "
             "sys.exit(1 if 'jax' in sys.modules else 0)"],
            capture_output=True, text=True, timeout=120,
        )
        assert out.returncode == 0, out.stderr[-2000:]


class TestAutoConfigure:
    def test_nnodes_promoted_from_env(self, clean_env, tmp_path):
        clean_env.setenv(EnvKey.NODE_NUM, "4:8")
        args = _args()
        auto_configure(args, dev_root=str(tmp_path), sys_pci_root=str(tmp_path / 'pci'))
        assert args.nnodes == "4:8"

    def test_cli_nnodes_wins_over_env(self, clean_env, tmp_path):
        clean_env.setenv(EnvKey.NODE_NUM, "8")
        args = _args("--nnodes", "2")
        auto_configure(args, dev_root=str(tmp_path), sys_pci_root=str(tmp_path / 'pci'))
        assert args.nnodes == "2"

    def test_device_count_exported_without_jax(self, clean_env, tmp_path):
        (tmp_path / "accel0").touch()
        (tmp_path / "accel1").touch()
        args = _args("--auto-config")
        auto_configure(args, dev_root=str(tmp_path), sys_pci_root=str(tmp_path / 'pci'))
        assert os.environ[EnvKey.DEVICE_COUNT_OVERRIDE] == "2"
        assert os.environ[EnvKey.ACCELERATOR] == "tpu"

    def test_explicit_device_override_kept(self, clean_env, tmp_path):
        (tmp_path / "accel0").touch()
        clean_env.setenv(EnvKey.DEVICE_COUNT_OVERRIDE, "7")
        args = _args("--auto-config")
        auto_configure(args, dev_root=str(tmp_path), sys_pci_root=str(tmp_path / 'pci'))
        assert os.environ[EnvKey.DEVICE_COUNT_OVERRIDE] == "7"

    def test_network_check_auto_on_at_4_nodes(self, clean_env, tmp_path):
        args = _args("--auto-config", "--nnodes", "4")
        auto_configure(args, dev_root=str(tmp_path), sys_pci_root=str(tmp_path / 'pci'))
        assert args.network_check

    def test_network_check_stays_off_small(self, clean_env, tmp_path):
        args = _args("--auto-config", "--nnodes", "2")
        auto_configure(args, dev_root=str(tmp_path), sys_pci_root=str(tmp_path / 'pci'))
        assert not args.network_check

    def test_init_timeout_scales_with_fleet(self, clean_env, tmp_path):
        args = _args("--auto-config", "--nnodes", "512")
        auto_configure(args, dev_root=str(tmp_path), sys_pci_root=str(tmp_path / 'pci'))
        assert int(os.environ[EnvKey.INIT_TIMEOUT]) == 300 + (512 - 64)

    def test_gated_off_without_flag(self, clean_env, tmp_path):
        (tmp_path / "accel0").touch()
        args = _args("--nnodes", "8")
        auto_configure(args, dev_root=str(tmp_path), sys_pci_root=str(tmp_path / 'pci'))
        assert EnvKey.DEVICE_COUNT_OVERRIDE not in os.environ
        assert not args.network_check
        assert EnvKey.INIT_TIMEOUT not in os.environ
