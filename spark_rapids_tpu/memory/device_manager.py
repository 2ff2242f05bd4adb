"""TPU device acquisition and HBM budget management.

Reference parity: GpuDeviceManager.scala —
- pick/acquire one accelerator per executor process (:98-127)
- initialize the memory pool at allocFraction x total (:152-198)
- pinned host staging pool (:200-206)
- per-task/thread device setup (:139-150, :231-242)

TPU differences (SURVEY.md section 7 hard part #4): XLA owns HBM and there is
no RMM-style alloc-failure callback, so the manager keeps an explicit byte
budget and the buffer stores spill *preemptively* before uploads instead of
reactively on allocation failure. The DeviceMemoryEventHandler analog is
`MemoryWatermark.ensure_headroom` (memory/spill.py).
"""

from __future__ import annotations

import logging
import threading
from typing import Optional

from spark_rapids_tpu import _jax_setup
import jax

from spark_rapids_tpu import conf as C

log = logging.getLogger(__name__)

# the cpu backend's allocator reports no limit: budget it like one v5e chip
_CPU_BACKEND_HBM_BYTES = 16 << 30


class TpuDeviceManager:
    """Singleton per process (reference: GpuDeviceManager object)."""

    _instance: Optional["TpuDeviceManager"] = None
    _lock = threading.Lock()

    def __init__(self, tpu_conf: "C.TpuConf"):
        self.conf = tpu_conf
        self.device = None
        self.platform = None
        self.hbm_total = 0
        self.hbm_budget = 0
        self._initialized = False
        # live-bytes high-water mark (start/stop_live_peak_tracking):
        # sampled at every device dispatch while tracking is on
        self._peak_lock = threading.Lock()
        self._live_peak = 0
        # bytes donated into consume-once kernels (note_donation)
        self._donated_bytes = 0

    # -- lifecycle -----------------------------------------------------------
    @classmethod
    def initialize(cls, tpu_conf: Optional["C.TpuConf"] = None) -> "TpuDeviceManager":
        """Acquire the accelerator and size the HBM budget (reference:
        GpuDeviceManager.initializeGpuAndMemory, GpuDeviceManager.scala:120)."""
        with cls._lock:
            if cls._instance is not None and cls._instance._initialized:
                return cls._instance
            mgr = cls(tpu_conf or C.TpuConf())
            mgr._do_init()
            cls._instance = mgr
            return mgr

    @classmethod
    def get(cls) -> "TpuDeviceManager":
        if cls._instance is None or not cls._instance._initialized:
            return cls.initialize()
        return cls._instance

    @classmethod
    def shutdown(cls) -> None:
        with cls._lock:
            cls._instance = None
        cls.clear_quarantine()

    def _do_init(self) -> None:
        devices = jax.devices()
        # one accelerator per process, like the 1-GPU-per-executor rule
        # (GpuDeviceManager.scala:98-112); multi-chip execution goes through
        # jax.sharding.Mesh in spark_rapids_tpu.parallel, not multiple
        # independent devices.
        self.device = devices[0]
        self.platform = self.device.platform
        # before the engine's first compile on any entry point
        _jax_setup.place_compile_cache(self.platform)
        override = self.conf.get(C.HBM_SIZE_OVERRIDE)
        if override:
            self.hbm_total = override
        else:
            self.hbm_total = self._detect_hbm(self.device)
        frac = self.conf.get(C.MEMORY_FRACTION)
        self.hbm_budget = int(self.hbm_total * frac)
        self._initialized = True
        log.info(
            "TpuDeviceManager: device=%s platform=%s hbm_total=%d budget=%d",
            self.device, self.platform, self.hbm_total, self.hbm_budget,
        )

    # -- error translation ---------------------------------------------------
    # markers of a device-memory exhaustion in backend runtime errors (XLA
    # raises XlaRuntimeError with a gRPC-style status prefix; the allocator
    # message wording varies by backend/version, so match broadly)
    _OOM_MARKERS = ("RESOURCE_EXHAUSTED", "RESOURCE EXHAUSTED",
                    "Out of memory", "out of memory", "OOM",
                    "Attempting to allocate")
    _TRANSIENT_MARKERS = ("ABORTED", "UNAVAILABLE", "DEADLINE_EXCEEDED",
                          "DATA_LOSS", "device disconnected",
                          "premature end of stream")
    # markers of the device itself being GONE (backend restart, ICI peer
    # loss, hardware reset) — checked BEFORE the transient family because
    # loss messages often carry UNAVAILABLE too, and the recovery path is
    # different: never retried in place, the session quarantines the
    # device and replays/degrades (docs/fault-tolerance.md self-healing)
    _DEVICE_LOSS_MARKERS = ("device lost", "Device lost", "DEVICE_RESET",
                            "backend restarted", "backend restart",
                            "peer is unreachable", "ICI peer loss",
                            "device has been reset",
                            "hardware failure")
    # backend exception type names that carry device-runtime failures
    # (matched by name: jaxlib layouts move across versions and the
    # translation must not hard-depend on them)
    _DEVICE_ERROR_TYPES = ("XlaRuntimeError", "JaxRuntimeError",
                           "InternalError", "PjRtError")

    @classmethod
    def translate_device_error(cls, e: BaseException):
        """Map a backend runtime error into the typed retryable hierarchy
        (engine/retry.py): RESOURCE_EXHAUSTED -> TpuRetryOOM, the
        unavailable/reset family -> TpuDeviceLostError (quarantine +
        replay, never retried in place), ABORTED/UNAVAILABLE ->
        TpuTransientDeviceError, anything else -> None (not a
        device-health failure; the caller re-raises). This is the TPU
        analog of the RMM failure callback classifying allocation
        failures for the retry state machine."""
        from spark_rapids_tpu.engine.retry import (
            TpuDeviceLostError,
            TpuRetryOOM,
            TpuTransientDeviceError,
        )

        if isinstance(e, (TpuRetryOOM, TpuTransientDeviceError)):
            return e
        tname = type(e).__name__
        if tname not in cls._DEVICE_ERROR_TYPES:
            return None
        msg = str(e)
        if any(m in msg for m in cls._OOM_MARKERS):
            return TpuRetryOOM(f"device OOM ({tname}): {msg}")
        if any(m in msg for m in cls._DEVICE_LOSS_MARKERS):
            return TpuDeviceLostError(f"device lost ({tname}): {msg}")
        if any(m in msg for m in cls._TRANSIENT_MARKERS):
            return TpuTransientDeviceError(
                f"transient device error ({tname}): {msg}")
        return None

    # -- device quarantine (self-healing, docs/fault-tolerance.md) -----------
    # A device a TpuDeviceLostError was rooted on is POISONED: the session
    # quarantines it (quarantine_device), the ICI mesh rebuilds on the
    # survivors (shuffle/ici.session_mesh filters quarantined ids), and
    # admission re-scales its byte budget so it stops pricing the lost
    # chip's HBM. Process-wide state, cleared with the shared runtime.
    _quarantined_ids: set = set()
    _quarantine_lock = threading.Lock()

    @classmethod
    def quarantine_device(cls, device=None, reason: str = "") -> int:
        """Mark `device` (default: the manager's own) poisoned; rebuilds
        the ICI mesh on the survivors and returns the healthy count."""
        if device is None:
            mgr = cls._instance
            device = mgr.device if mgr is not None else None
        did = getattr(device, "id", 0)
        with cls._quarantine_lock:
            already = did in cls._quarantined_ids
            cls._quarantined_ids.add(did)
        if not already:
            log.warning("device %s quarantined: %s", did,
                        reason or "device loss")
            from spark_rapids_tpu.shuffle import ici as _ici

            _ici.reset_mesh()
        return cls.healthy_device_count()

    @classmethod
    def is_quarantined(cls, device) -> bool:
        with cls._quarantine_lock:
            return getattr(device, "id", 0) in cls._quarantined_ids

    @classmethod
    def quarantined_count(cls) -> int:
        with cls._quarantine_lock:
            return len(cls._quarantined_ids)

    @classmethod
    def healthy_devices(cls) -> list:
        with cls._quarantine_lock:
            bad = set(cls._quarantined_ids)
        try:
            devs = jax.devices()
        except Exception:
            return []
        return [d for d in devs if getattr(d, "id", 0) not in bad]

    @classmethod
    def healthy_device_count(cls) -> int:
        return len(cls.healthy_devices())

    @classmethod
    def clear_quarantine(cls) -> None:
        with cls._quarantine_lock:
            cls._quarantined_ids.clear()

    @staticmethod
    def _detect_hbm(device) -> int:
        """The device's memory limit as its allocator reports it. Only the
        cpu backend, which reports none, gets the constant: an accelerator
        without a limit is an error, not a guessed 16 GiB."""
        if device.platform == "cpu":
            return _CPU_BACKEND_HBM_BYTES
        stats = device.memory_stats() or {}
        for key in ("bytes_limit", "bytes_reservable_limit"):
            if stats.get(key):
                return int(stats[key])
        raise RuntimeError(
            f"{device} ({device.platform}) reports no memory limit "
            f"(memory_stats: {sorted(stats)}); set "
            f"{C.HBM_SIZE_OVERRIDE.key} to size the HBM budget by hand")

    # -- accounting ----------------------------------------------------------
    def note_donation(self, nbytes: int) -> None:
        """Account input bytes donated into a consume-once kernel
        (docs/async-execution.md). live-bytes tracking needs no manual
        correction — the backend allocator's bytes_in_use drops when the
        program consumes the donated buffers, and the live_arrays
        fallback stops seeing deleted arrays — but the tally (a) feeds
        the per-query donatedBytes metric and (b) records that these
        bytes were never spill-store candidates: donation sites gate on
        ColumnarBatch.owned, which store-tracked batches never carry, so
        PR 4's synchronous_spill can never try to spill a donated-away
        buffer."""
        from spark_rapids_tpu.utils import metrics as M

        M.record_donated_bytes(int(nbytes))
        with self._peak_lock:
            self._donated_bytes += int(nbytes)

    @property
    def donated_bytes(self) -> int:
        """Total bytes donated into kernels since process start."""
        with self._peak_lock:
            return self._donated_bytes

    def bytes_in_use(self) -> int:
        try:
            stats = self.device.memory_stats()
            if stats and "bytes_in_use" in stats:
                return int(stats["bytes_in_use"])
        except Exception:
            pass
        return 0

    def live_bytes(self) -> int:
        """Current device-resident bytes: the backend allocator's
        bytes_in_use when the platform reports it, else the sum of live
        jax array buffers on this platform (the CPU-backend fallback —
        its allocator exposes no stats)."""
        got = self.bytes_in_use()
        if got:
            return got
        try:
            total = 0
            for arr in jax.live_arrays(self.platform):
                total += int(getattr(arr, "nbytes", 0) or 0)
            return total
        except Exception:
            return 0

    # -- live-bytes high-water mark (resource-analyzer accuracy tests and
    # bench.py estimate-drift reporting measure against this) ----------------
    def start_live_peak_tracking(self) -> None:
        """Begin sampling the live-bytes high-water mark at every device
        dispatch. Off by default: the sampler walks the backend's live
        buffers, which is measurement machinery, not a hot-path default."""
        from spark_rapids_tpu.utils import metrics as M

        with self._peak_lock:
            self._live_peak = self.live_bytes()
        M.set_dispatch_hook(self._sample_live_peak)

    def stop_live_peak_tracking(self) -> int:
        """Stop sampling and return the observed high-water mark."""
        from spark_rapids_tpu.utils import metrics as M

        M.set_dispatch_hook(None)
        self._sample_live_peak()
        with self._peak_lock:
            return self._live_peak

    def _sample_live_peak(self) -> None:
        now = self.live_bytes()
        with self._peak_lock:
            if now > self._live_peak:
                self._live_peak = now

    @property
    def live_bytes_peak(self) -> int:
        with self._peak_lock:
            return self._live_peak

    @property
    def is_tpu(self) -> bool:
        return self.platform not in ("cpu",)
