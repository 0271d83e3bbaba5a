"""Cut a small recorded trace for the tests out of a real one.

    python3 tpubench/tools/cut_trace.py <kept trace.json> <offset_us> <length_us> \
        <pattern>[,<pattern>...]

``kept trace.json`` is what ``TPUBENCH_KEEP_TRACE=<path>`` makes a traced
run write (the neutral form of ``harness/trace.py``). The cut keeps the
device events that start inside [offset, offset + length) of the window,
makes that interval the window, and writes beside the events what they
reduce to, worked out here a second way: by painting a timeline of
nanoseconds, not by merging intervals.
"""

import json
import pathlib
import re
import sys

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from tpubench.harness import trace as T  # noqa: E402


def main(argv):
    src, offset_us, length_us, patterns = argv
    full = json.loads(pathlib.Path(src).read_text())
    w0, _ = T.window_ns(full)
    a = w0 + int(float(offset_us) * 1000)
    b = a + int(float(length_us) * 1000)
    cut = {}
    for plane in T.device_planes(full):
        cut[plane] = {
            line: [e for e in events if a <= e[1] and e[1] + e[2] <= b]
            for line, events in full[plane].items()}
    cut["/host:CPU"] = {"python": [[T.WINDOW_SPAN, a, b - a]]}
    planes = T.device_planes(cut)
    busy = 0
    matching = {}
    for plane in planes:
        paint = np.zeros(b - a, bool)
        for _, s, d in cut[plane].get(T.OPS_LINE, []):
            paint[s - a:s - a + d] = True
        busy += int(paint.sum())
    for pattern in patterns.split(","):
        total = count = 0
        for plane in planes:
            for name, _, d in cut[plane].get(T.OPS_LINE, []):
                if re.search(pattern, name):
                    total, count = total + d, count + 1
        matching[pattern] = [total / len(planes) / 1e9, count / len(planes)]
    out = {"trace": cut,
           "by_hand": {"window_s": (b - a) / 1e9,
                       "busy_s": busy / len(planes) / 1e9,
                       "matching": matching}}
    dest = ROOT / "tpubench" / "tests" / "data" / "recorded_trace.json"
    dest.parent.mkdir(exist_ok=True)
    dest.write_text(json.dumps(out))
    print(json.dumps(out["by_hand"]), dest.stat().st_size, "bytes")


if __name__ == "__main__":
    main(sys.argv[1:])
