"""Device-backend loss detection: bounded fences + latched revocation.

Capability analog of the reference's DRIVER-INITIATED revocation: there,
cuMemFree or process death fires the NVIDIA callback, which blocks until
in-flight DMA drains and then tears the mapping down
(`kmod/pmemmap.c:149-208`) — the *other* side of the link can kill a
registration.  Here the failure to guard against is the device runtime
dying under us: a hung backend turns every ``block_until_ready`` into
an unbounded hang.

The :class:`BackendMonitor` makes that a *detected, latched* failure
instead of a hang:

* :meth:`fence` — ``block_until_ready`` with a bounded timeout (config
  ``backend_fence_timeout``): the wait runs in a helper thread, and a
  deadline miss (or a PJRT runtime error) latches backend loss.
* On loss, every registered :class:`.registry.HbmRegistry` revokes its
  buffers with ENODEV (in-flight transfers are dead with the backend —
  there is nothing to drain), and every subsequent fence fails fast with
  ENODEV so teardown paths cannot re-hang.
* The latch is reported by ``strom_check`` and surfaces to engine
  consumers as a reaped task error through the staging pipeline's
  cleanup (first-error discipline, ``kmod/nvme_strom.c:770-776``).

A test fault hook (installed by :func:`..testing.fake.backend_fault`)
injects a hang or a runtime error at the fence, so the whole path is
testable without hardware.
"""

from __future__ import annotations

import errno as _errno
import threading
from typing import Callable, List, Optional

from ..api import StromError
from ..config import config
from ..log import pr_warn

__all__ = ["BackendMonitor", "monitor", "aliased_device_put"]


def aliased_device_put(host, devlike):
    """``device_put`` that MAY alias *host* — the zero-copy landing leg.

    The staging ring must never alias its reusable slots (the next SSD
    DMA would overwrite live device state; ``staging.owned_if_cpu``
    copies first).  A :class:`~.registry.LandingBuffer` is the opposite
    case: the buffer is OWNED by the destination for the array's whole
    lifetime, so the CPU backend's zero-copy of a page-aligned view is
    exactly the reference's BAR1 behaviour (`kmod/pmemmap.c`) — the
    landed bytes ARE the device array, nothing is touched twice.
    Accelerator backends copy host→HBM here like everywhere else; the
    landing planner routes those staged instead."""
    import jax
    return jax.device_put(host, devlike)


class BackendMonitor:
    """Process-wide device-backend health latch."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._lost: Optional[str] = None
        self._registries: List[object] = []
        self._fault: Optional[Callable[[str], None]] = None

    # -- state -------------------------------------------------------------
    def lost(self) -> Optional[str]:
        """The latched loss reason, or None while healthy."""
        with self._lock:
            return self._lost

    def check(self) -> None:
        """Raise ENODEV if the backend is latched lost."""
        why = self.lost()
        if why is not None:
            raise StromError(_errno.ENODEV, f"device backend lost: {why}")

    def register_registry(self, registry) -> None:
        """Registries to revoke on loss (the global one self-registers)."""
        with self._lock:
            if registry not in self._registries:
                self._registries.append(registry)

    def mark_lost(self, why: str) -> None:
        """Latch loss (first reason wins) and revoke registered buffers."""
        with self._lock:
            if self._lost is not None:
                return
            self._lost = why
            registries = list(self._registries)
        pr_warn("device backend LOST: %s — revoking registered buffers", why)
        for reg in registries:
            try:
                reg.revoke_all(why)
            except Exception as e:  # noqa: BLE001 - loss path must not throw
                pr_warn("revoke_all failed: %s", e)

    def reset(self) -> None:
        """Clear the latch (tests / an operator after transport recovery);
        already-revoked buffers stay revoked — re-register destinations."""
        with self._lock:
            self._lost = None

    # -- the bounded fence -------------------------------------------------
    def fence(self, arr, *, what: str = "h2d",
              timeout_s: Optional[float] = None):
        """``arr.block_until_ready()`` with loss detection.

        A latched loss fails immediately (teardown paths must never
        re-hang); a wait past ``backend_fence_timeout`` seconds (0 =
        unbounded) or a runtime error from the fence latches loss and
        raises ENODEV.  Returns *arr* so call sites can chain."""
        self.check()
        if timeout_s is None:
            timeout_s = float(config.get("backend_fence_timeout"))
        fault = self._fault
        try:
            if fault is None and timeout_s > 0:
                # fast path: a ready array needs no bounding machinery —
                # the helper thread only exists for genuinely pending
                # fences, so the per-batch cost in the healthy steady
                # state stays at one is_ready() call
                try:
                    if arr.is_ready():
                        return arr
                except AttributeError:
                    pass
            if timeout_s <= 0:
                if fault is not None:
                    fault(what)
                arr.block_until_ready()
                return arr
            err: List[BaseException] = []

            def _wait() -> None:
                # the injected fault runs HERE so a simulated wedge
                # (hook that sleeps) is cut off by the bounded join
                # exactly like a real hung block_until_ready
                try:
                    if fault is not None:
                        fault(what)
                    arr.block_until_ready()
                except BaseException as e:  # noqa: BLE001 - forwarded below
                    err.append(e)

            t = threading.Thread(target=_wait, name="strom-fence",
                                 daemon=True)
            t.start()
            t.join(timeout_s)
            if t.is_alive():
                raise TimeoutError(
                    f"{what} fence exceeded {timeout_s:g}s "
                    f"(backend_fence_timeout)")
            if err:
                raise err[0]
            return arr
        except StromError:
            raise
        except (KeyboardInterrupt, SystemExit):
            raise        # an interrupt is the USER, never the backend
        except BaseException as e:
            # classify before latching: a deferred allocation failure
            # surfacing at the fence is a per-array condition, not
            # transport death — poisoning the whole process over it
            # would turn one oversized batch into permanent ENODEV
            msg = str(e)
            if "RESOURCE_EXHAUSTED" in msg or "out of memory" in msg.lower():
                raise StromError(_errno.ENOMEM,
                                 f"device allocation failed during "
                                 f"{what}: {e}") from e
            self.mark_lost(f"{what}: {e}")
            raise StromError(_errno.ENODEV,
                             f"device backend lost during {what}: {e}") \
                from e

    # -- test fault injection ---------------------------------------------
    def _set_fault(self, hook: Optional[Callable[[str], None]]) -> None:
        self._fault = hook


#: process-global monitor; the global HbmRegistry self-registers with it
monitor = BackendMonitor()
