"""How tests reach a DeviceWindows' host shadow, whichever form holds it
(the native slot-indexed mirror or the dict; matcher/windows.py): read
through `DeviceWindows.shadow_items()`, plant through `plant`, and look
at the queued device restores through `pending_restore_slots`."""

from collections import OrderedDict

import numpy as np


def shadow(dw):
    """ip -> (rule_id -> (hits, start_s, start_ns)), format_states order."""
    return dw.shadow_items()


def plant(dw, ip, vec):
    """`shadow.setdefault(ip, vec)`: give `ip` the record an absorb would
    have left, unless it holds one — in the mirror at its slot while it
    is resident, else in the dict."""
    if ip in dw.shadow_items():
        return
    vec = OrderedDict(vec)
    with dw._lock:
        if dw._mirror is None:
            dw._shadow[ip] = vec
            return
        slot = int(dw._sm.find_batch([ip])[0])
        if slot >= 0:
            dw._mirror.install(slot, vec)
        else:
            dw._shadow[ip] = vec
            dw._shadow_stamp[ip] = dw._mirror.next_stamp()


def pending_restore_slots(dw):
    """Slots whose counters are queued to re-enter the device, in order."""
    if dw._mirror is None:
        return [slot for slot, _ in dw._pending_restore]
    return [int(s) for part in dw._pending_restore for s in part[0]]


def spans_of(ips):
    """The distinct addresses `ips` as the byte spans a native parse's
    work set hands the pass (slotmgr.AddressSpans), through the merge
    that makes them there — each address its own row of one shard."""
    from banjax_tpu.native import slotmgr

    spans, inverse = slotmgr.merge_spans(
        [(slotmgr.encode_ips(ips), np.arange(len(ips), dtype=np.int64))]
    )
    assert inverse.tolist() == list(range(len(ips)))
    return spans


FORMS = {"strings": None, "spans": spans_of}
