"""Accelerator detection and chip ownership — TPU as a first-class
scheduler resource.

Reference: python/ray/_private/accelerators/tpu.py (398 LoC) detects TPU
chips via GKE env vars / GCE metadata and advertises a pod-slice head
resource ``TPU-{pod_type}-head`` so one task can claim a whole slice
(tpu.py:382).

Detection never imports jax: a process that initialises the TPU backend
holds the chip, and the detecting process (driver ``init``, head, node
daemon) is often not the one that will compute. Chips are counted from
the device nodes this process could open; environment and metadata only
name the slice. ``ChipLeases`` then keeps each chip with exactly one
process.
"""

from __future__ import annotations

import glob
import logging
import math
import os
import sys
import threading

from ray_tpu.exceptions import ChipOwnershipError

logger = logging.getLogger("ray_tpu")

# Valid per-host chip counts (reference: tpu.py:13) — a metadata value
# outside this set means a misconfigured node, not more chips.
_VALID_CHIPS_PER_HOST = (1, 2, 4, 8)

# The metadata server's link-local address, not its name: getaddrinfo is
# not bounded by a socket timeout and stalls on a host without DNS.
_GCE_METADATA_URL = ("http://169.254.169.254/computeMetadata"
                     "/v1/instance/attributes/")

_GOOGLE_PCI_VENDOR = "0x1ae0"
_TPU_PCI_DEVICES = {"0x0027": "v3", "0x005e": "v4", "0x0062": "v5p",
                    "0x0063": "v5e", "0x006f": "v6e", "0x0076": "tpu7x"}


def _read(path: str) -> str:
    try:
        with open(path) as f:
            return f.read().strip()
    except OSError:
        return ""


def local_chips(sys_root: str = "/sys",
                dev_root: str = "/dev") -> tuple[int, str | None]:
    """(count, generation) of the TPU chips this process could open,
    from sysfs and device nodes alone.

    A chip counts when its PCI function is present AND its device node
    is: ``/dev/accel*`` (v4 and older drivers) or the VFIO group of the
    function (v5e and newer). A machine that is handed one chip of a
    four-chip host lists four PCI functions and one VFIO group, and the
    environment still describes the whole host.
    """
    generation = None
    functions = []
    for vendor in glob.glob(f"{sys_root}/bus/pci/devices/*/vendor"):
        if _read(vendor) != _GOOGLE_PCI_VENDOR:
            continue
        function = os.path.dirname(vendor)
        kind = _TPU_PCI_DEVICES.get(_read(f"{function}/device"))
        if kind is not None:
            generation = kind
            functions.append(function)
    if not functions:
        return 0, None
    accel = glob.glob(f"{dev_root}/accel[0-9]*")
    if accel:
        return min(len(accel), len(functions)), generation
    groups = {os.path.basename(os.path.realpath(f"{fn}/iommu_group"))
              for fn in functions if os.path.exists(f"{fn}/iommu_group")}
    count = sum(os.path.exists(f"{dev_root}/vfio/{g}") for g in groups)
    return count, generation


def _on_gce() -> bool:
    """Cheap LOCAL check for Google Compute Engine (DMI product name)."""
    return "Google" in _read("/sys/class/dmi/id/product_name")


def _gce_metadata(key: str, timeout_s: float = 0.5) -> str | None:
    """GCE instance-attribute lookup (reference: tpu.py GCE branch).
    ``TPU_SKIP_MDS_QUERY`` is libtpu's own switch for hosts without a
    metadata server; it is honoured here too."""
    if os.environ.get("TPU_SKIP_MDS_QUERY") or not _on_gce():
        return None
    import urllib.error
    import urllib.request

    try:
        req = urllib.request.Request(
            _GCE_METADATA_URL + key,
            headers={"Metadata-Flavor": "Google"})
        with urllib.request.urlopen(req, timeout=timeout_s) as resp:
            return resp.read().decode()
    except (urllib.error.URLError, OSError, ValueError):
        return None  # no egress / attribute absent


def detect_tpu_topology() -> dict | None:
    """GKE/GCE TPU topology from environment metadata, or None.

    GKE injects TPU_ACCELERATOR_TYPE (e.g. "v5litepod-16") and
    TPU_WORKER_ID / TPU_WORKER_HOSTNAMES (reference: tpu.py:14-28);
    plain GCE TPU-VMs expose the same through the metadata server.
    Returns {"accelerator_type", "worker_id", "num_workers",
    "chips_per_host"}.
    """
    accel = os.environ.get("TPU_ACCELERATOR_TYPE") \
        or _gce_metadata("accelerator-type")
    if not accel:
        return None
    raw_worker = os.environ.get("TPU_WORKER_ID") \
        or _gce_metadata("agent-worker-number") or "0"
    try:
        worker_id = int(raw_worker.strip())
    except ValueError:
        # Corrupt metadata (captive portal, proxy page): assume worker
        # 0 rather than failing the whole node's resource detection.
        worker_id = 0
    hostnames = os.environ.get("TPU_WORKER_HOSTNAMES") \
        or _gce_metadata("worker-network-endpoints") or ""
    num_workers = max(1, len([h for h in hostnames.split(",") if h]))
    # Per-host chip count from TPU_CHIPS_PER_HOST_BOUNDS ("2,2,1" =>
    # 4 chips — reference: tpu.py:44), else from the accelerator type.
    chips = None
    bounds = os.environ.get("TPU_CHIPS_PER_HOST_BOUNDS")
    if bounds:
        try:
            n = 1
            for part in bounds.split(","):
                n *= int(part)
            chips = n
        except ValueError:
            chips = None
    if chips is None:
        try:
            total = int(accel.rsplit("-", 1)[1])
            # v2/v3/v4/v5p accelerator-type suffixes count TENSORCORES
            # (2 per chip); v5e (v5litepod) and v6e suffixes count
            # chips (reference: tpu.py's per-generation tables).
            gen = accel.split("-")[0].lower()
            if gen in ("v2", "v3", "v4", "v5p"):
                total = max(1, total // 2)
            chips = max(1, total // num_workers)
        except (ValueError, IndexError):
            chips = 4
    if chips not in _VALID_CHIPS_PER_HOST:
        logger.warning(
            "TPU metadata reports %s chips/host (valid: %s); clamping",
            chips, _VALID_CHIPS_PER_HOST)
        chips = min(_VALID_CHIPS_PER_HOST,
                    key=lambda v: abs(v - chips))
    return {
        "accelerator_type": accel,
        "worker_id": int(worker_id),
        "num_workers": num_workers,
        "chips_per_host": chips,
    }


def detect_resources() -> dict[str, float]:
    """This host's accelerator resources. Reads the environment, sysfs
    and (on GCE only) the metadata server; never imports jax."""
    resources: dict[str, float] = {}
    override = os.environ.get("RAY_TPU_NUM_TPU_CHIPS")
    if override is not None:
        count = float(override)
        if count > 0:
            resources["TPU"] = count
        return resources
    if os.environ.get("RAY_TPU_SKIP_TPU_DETECTION"):
        return resources
    count, generation = local_chips()
    if count == 0:
        # No chip here: whatever the environment or the metadata say of
        # a slice, this process cannot open one — and a host without
        # chips never waits on the metadata server.
        return resources
    resources["TPU"] = float(count)
    # The slice's name comes from GKE/GCE metadata (reference:
    # tpu.py:14-44, :382); the count above is what is really here.
    topo = detect_tpu_topology()
    if topo is None:
        resources[f"TPU-{generation}-head"] = 1.0
    elif topo["worker_id"] == 0:
        # Pod-slice gang resource on worker 0 ONLY: exactly one task
        # per slice can claim the whole gang (tpu.py:363-382).
        resources[f"TPU-{topo['accelerator_type']}-head"] = 1.0
    return resources


def visible_chip_env(chip_ids: list[int]) -> dict[str, str]:
    """Env isolating a worker to specific chips (reference: tpu.py:30
    TPU_VISIBLE_CHIPS and its per-count bounds)."""
    bounds = {1: "1,1,1", 2: "1,2,1", 4: "2,2,1", 8: "2,4,1"}
    if len(chip_ids) not in bounds:
        raise ValueError(
            f"a process can be given 1, 2, 4 or 8 chips, not "
            f"{len(chip_ids)}")
    return {
        "TPU_VISIBLE_CHIPS": ",".join(str(c) for c in chip_ids),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": bounds[len(chip_ids)],
        "TPU_PROCESS_BOUNDS": "1,1,1",
    }


def process_holds_tpu() -> bool:
    """Whether THIS process has initialised a TPU backend (user code may
    have, before or beside the runtime): it then holds every chip it
    could see, for life."""
    jax = sys.modules.get("jax")
    if jax is None:
        return False
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized() \
        and jax.default_backend() == "tpu"


def tpu_chip_demand(resources: dict, num_chips: int) -> int:
    """Whole chips a resource demand needs: ``TPU`` rounded up, or every
    chip of the host for a bare ``TPU-*-head`` gang demand."""
    if resources.get("TPU"):
        return math.ceil(resources["TPU"])
    if any(k.startswith("TPU") and v for k, v in resources.items()):
        return num_chips
    return 0


class ChipLeases:
    """Which process owns each TPU chip of one host: exactly one.

    Either the host process (driver or node daemon) runs device work on
    its own threads — thread actors, in-process TPU tasks — and then
    holds every chip for life, because a JAX backend never gives a chip
    back; or whole chips are leased to child processes, each of which
    sees only its own through ``visible_chip_env``. Mixing the two would
    put two processes on one chip, where the second fails or hangs, so
    it is refused with ``ChipOwnershipError`` instead.
    """

    def __init__(self, num_chips: int):
        self.num_chips = int(num_chips)
        self._lock = threading.Lock()
        self._in_process = False
        self._leased: dict[object, list[int]] = {}

    def claim_in_process(self, what: str) -> None:
        """The host process is about to run TPU work itself."""
        with self._lock:
            if self._leased:
                raise ChipOwnershipError(
                    f"{what} would run TPU work inside this process, "
                    f"but chips {sorted(sum(self._leased.values(), []))}"
                    f" are leased to child processes; one process owns "
                    f"a chip at a time")
            self._in_process = True

    def lease(self, holder: object, n_chips: int, what: str) -> list[int]:
        """Lease ``n_chips`` whole chips to a child process."""
        with self._lock:
            if self._in_process or process_holds_tpu():
                raise ChipOwnershipError(
                    f"{what} needs a TPU in its own process, but this "
                    f"process (pid {os.getpid()}) already holds the "
                    f"host's chips; run it as a thread actor here, or "
                    f"keep this process off the TPU")
            taken = {c for ids in self._leased.values() for c in ids}
            free = [c for c in range(self.num_chips) if c not in taken]
            if n_chips > len(free):
                raise ChipOwnershipError(
                    f"{what} needs {n_chips} whole chip(s) of its own; "
                    f"{len(free)} of {self.num_chips} are free (a chip "
                    f"is not shared between processes)")
            self._leased[holder] = free[:n_chips]
            return list(self._leased[holder])

    def release(self, holder: object) -> None:
        with self._lock:
            self._leased.pop(holder, None)
