"""Accelerator sniffing WITHOUT initializing JAX.

Reference analog: ``ElasticLaunchConfig.auto_configure_params`` reads
``torch.cuda.get_device_name()`` / ``device_count()`` in the launcher
process (dlrover/python/elastic_agent/torch/training.py:143-157). On TPU
that translation would be a bug: libtpu grants EXCLUSIVE chip access to
the first process that initializes it, so a launcher or agent that calls
``jax.local_device_count()`` steals the chips from the trainer child it
is about to spawn. Instead we look at what the kernel already exposes:
the TPU driver's ``/dev/accel*`` nodes (v2-v4 PCI hosts); then, on
v5+ hosts where the chips are VFIO-bound, the ``/dev/vfio/<group>``
nodes whose IOMMU group holds a Google (vendor 0x1ae0) function — the
groups this process can actually open, which on a one-chip VM of a
four-chip host is one although sysfs shows four functions; last a
sysfs PCI scan for Google *processing accelerator* (class 0x1200xx)
functions or known TPU device ids — the check matters because gVNIC
NICs share Google's vendor id.

The returned count uses JAX *device* semantics, not chip semantics:
v2/v3 chips carry two TensorCores each (two JAX devices per chip,
recognized by their PCI device ids), while v4+ run megacore (one).
"""

from __future__ import annotations

import glob
import os

from dlrover_tpu.common.log import get_logger

__all__ = ["sniff_accelerator"]

logger = get_logger(__name__)

_GOOGLE_PCI_VENDOR = "0x1ae0"
_PCI_CLASS_PROCESSING_ACCEL = "0x1200"  # PCI class 0x12, subclass 0x00
# PCI device id -> JAX devices (TensorCores) per chip. v2/v3 expose two
# cores per chip; v4+ (megacore) and the v5/v6 families expose one.
_CORES_PER_CHIP = {"0x0027": 2, "0x0037": 2}
# TPU functions that report an unassigned PCI class: v5e (0x0063) shows
# class 0xff0000 on the v5litepod hosts this was brought up on
_TPU_PCI_DEVICE_IDS = {"0x0063"}


def _read(path: str) -> str:
    try:
        with open(path) as f:
            return f.read().strip().lower()
    except OSError:
        return ""


def _chip_devices(pci_dir: str) -> int:
    """JAX devices contributed by the chip behind one PCI function."""
    return _CORES_PER_CHIP.get(_read(os.path.join(pci_dir, "device")), 1)


def sniff_accelerator(
    dev_root: str = "/dev",
    sys_pci_root: str = "/sys/bus/pci/devices",
    sys_accel_root: str = "/sys/class/accel",
    sys_iommu_root: str = "/sys/kernel/iommu_groups",
) -> tuple[str, int]:
    """Return ``(kind, local_device_count)`` with ``kind`` one of
    ``"tpu"`` / ``"cpu"``; never touches the accelerator.

    The roots are injectable for tests. CPU counts as 1 device: the
    JAX CPU backend presents one device per process unless
    ``xla_force_host_platform_device_count`` says otherwise, which the
    caller controls.
    """
    # numbered nodes only, and never the bare /dev/accel DIRECTORY the
    # generic Linux compute-accelerator subsystem creates (Intel NPU,
    # Habana, ... hosts) — that one is not a TPU
    accels = [
        p
        for p in glob.glob(os.path.join(dev_root, "accel[0-9]*"))
        if not os.path.isdir(p)
    ]
    if accels:
        total = 0
        for node in accels:
            # /sys/class/accel/accelN/device is a symlink to the PCI
            # function; unreadable (older driver layouts) -> megacore
            pci_dir = os.path.join(
                sys_accel_root, os.path.basename(node), "device"
            )
            if not _read(os.path.join(pci_dir, "device")):
                # on a v2/v3 host this defaults a 2-TensorCore chip to
                # 1 device; say so, or the undercount is undiagnosable
                logger.warning(
                    "sysfs PCI link %s for %s is unreadable; counting "
                    "the chip as megacore (1 JAX device) — set "
                    "DLROVER_TPU_DEVICE_COUNT to override an undercount",
                    pci_dir, node,
                )
            total += _chip_devices(pci_dir)
        return "tpu", total
    # VFIO-bound chips: one /dev/vfio/<group> node per chip this
    # process may open (the bare /dev/vfio/vfio container is not one)
    total = 0
    for node in glob.glob(os.path.join(dev_root, "vfio", "[0-9]*")):
        group = os.path.join(sys_iommu_root, os.path.basename(node),
                             "devices", "*")
        for dev in glob.glob(group):
            if _read(os.path.join(dev, "vendor")) == _GOOGLE_PCI_VENDOR:
                total += _chip_devices(dev)
    if total:
        return "tpu", total
    for dev in glob.glob(os.path.join(sys_pci_root, "*")):
        if _read(os.path.join(dev, "vendor")) != _GOOGLE_PCI_VENDOR:
            continue
        if _read(os.path.join(dev, "class")).startswith(
            _PCI_CLASS_PROCESSING_ACCEL
        ) or _read(os.path.join(dev, "device")) in _TPU_PCI_DEVICE_IDS:
            total += _chip_devices(dev)
    if total:
        return "tpu", total
    return "cpu", 1
