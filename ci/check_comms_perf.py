#!/usr/bin/env python
"""Counter-based comms-perf smoke for the dist_async fast path.

Loopback MB/s is wall-clock — useless as a CI gate on a noisy shared
host. This check pins the fast path's *structural* properties, straight from the
``kv.stats()`` counters, so a regression that quietly reintroduces a
copy, a per-key frame, or an unbounded window fails deterministically:

1. **Wire overhead is bounded**: one push of an N-byte part puts at
   most N + _SLACK bytes on the wire (pickle-5 out-of-band framing —
   the payload must ride as ONE raw buffer, never re-encoded into the
   body, and never split into per-chunk frames).
2. **Small keys coalesce**: a 64-key push of 1 KB values costs at most
   _FRAMES_MAX frames (one multi frame per server + slack), not 64 —
   and all 64 sub-pushes are counted coalesced.
3. **The pipelined window is bounded**: in-flight high-water never
   exceeds MXTPU_PS_WINDOW.
4. **The same-process shortcut is really zero-wire**: with
   MXTPU_PS_LOCAL on, the same pushes move ZERO wire bytes and are
   counted as local requests.

Run: ``JAX_PLATFORMS=cpu python ci/check_comms_perf.py`` (wired into
``ci/run_ci.sh fast``). No timing, no thresholds measured in seconds.
"""
from __future__ import annotations

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["MXTPU_PS_LOCAL"] = "0"       # start on the wire
os.environ["MXTPU_PS_HEARTBEAT"] = "0"

sys.path.insert(0, os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..")))

import numpy as np                                    # noqa: E402

import mxtpu as mx                                    # noqa: E402
from mxtpu import kvstore_async as ka                 # noqa: E402

# per-push wire slack: frame head (8+4+8), the pickled command tuple
# (op/key/clock/origin/seq), and the ack frame — generous 4x margin so
# a pickle detail can move without breaking CI, while a payload COPY
# into the body (2x bytes) still fails loudly
_SLACK = 2048
_FRAMES_MAX = 4           # frames for a 64-small-key push (1 multi + ack
#                           slack); 64 individual frames must fail


def _delta(kv, field, before):
    return kv._stats.snapshot()[field] - before[field]


def main():
    failures = []
    srv = ka.ParameterServer().start()
    os.environ["MXTPU_PS_ADDRS"] = srv.address
    kv = mx.kv.create("dist_async")
    try:
        # -- 1: bounded overhead for one dense part -------------------
        n = 1 << 20                                   # 1 MB, one part
        arr = mx.nd.array(np.ones(n // 4, "f"))
        kv.init("big", arr)
        before = kv._stats.snapshot()
        kv.push("big", arr)
        sent = _delta(kv, "bytes_sent", before)
        if not n <= sent <= n + _SLACK:
            failures.append(
                "push of %d payload bytes put %d on the wire "
                "(allowed <= payload + %d): a copy or re-encode snuck "
                "into the send path" % (n, sent, _SLACK))

        # pull: the reply must also be ~payload-sized
        before = kv._stats.snapshot()
        out = mx.nd.zeros(arr.shape)
        kv.pull("big", out=out)
        got = _delta(kv, "bytes_recv", before)
        if not n <= got <= n + _SLACK:
            failures.append(
                "pull of %d payload bytes read %d off the wire "
                "(allowed <= payload + %d)" % (n, got, _SLACK))

        # -- 2: 64 small keys coalesce into a handful of frames -------
        keys = ["s%02d" % i for i in range(64)]
        vals = [mx.nd.array(np.full(256, float(i), "f")) for i in range(64)]
        kv.init(keys, vals)
        before = kv._stats.snapshot()
        kv.push(keys, vals)
        frames = _delta(kv, "frames_sent", before)
        subs = _delta(kv, "coalesced_subs", before)
        if frames > _FRAMES_MAX:
            failures.append(
                "64-small-key push cost %d frames (allowed <= %d): "
                "coalescing is broken" % (frames, _FRAMES_MAX))
        if subs != 64:
            failures.append(
                "expected all 64 small pushes coalesced, counted %d"
                % subs)

        # -- 3: the in-flight window is bounded -----------------------
        hwm = kv._stats.snapshot()["inflight_hwm"]
        if hwm > ka._WINDOW:
            failures.append(
                "in-flight high-water %d exceeds MXTPU_PS_WINDOW=%d"
                % (hwm, ka._WINDOW))

        # -- 4: the same-process shortcut moves zero wire bytes -------
        ka._LOCAL_ON = True
        try:
            before = kv._stats.snapshot()
            kv.push("big", arr)
            if _delta(kv, "bytes_sent", before) != 0:
                failures.append(
                    "local-transport push still moved wire bytes")
            if _delta(kv, "local_reqs", before) < 1:
                failures.append(
                    "local-transport push not counted as local")
        finally:
            ka._LOCAL_ON = False
    finally:
        kv.close()
        srv.stop()

    if failures:
        print("check_comms_perf: FAIL")
        for f in failures:
            print("  - " + f)
        return 1
    print("check_comms_perf: OK (overhead/coalescing/window/local "
          "counters all within contract)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
