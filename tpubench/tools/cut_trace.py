"""Cut a small recorded trace for the tests out of a real one.

    python3 tpubench/tools/cut_trace.py <kept trace.json> <offset_us> <length_us> \
        <pattern>[,<pattern>...] [<destination.json> [<name_chars>]]

``kept trace.json`` is what ``TPUBENCH_KEEP_TRACE=<path>`` makes a traced
run write (the neutral form of ``harness/trace.py``). The cut keeps the
device events that lie inside [offset, offset + length) of the window and
the host spans that overlap it (clipped to it), makes that interval the
window, and writes beside the events what they reduce to, worked out here
a second way: by painting a timeline of nanoseconds, not by merging
intervals or sweeping span edges. ``name_chars`` cuts every device
event's name to that many characters (a serving trace names an event by
its whole HLO instruction). The destination defaults to the training
trace the tests pin the reduction on.
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
    src, offset_us, length_us, patterns = argv[:4]
    dest = pathlib.Path(argv[4]) if len(argv) > 4 else (
        ROOT / "tpubench" / "tests" / "data" / "recorded_trace.json")
    name_chars = int(argv[5]) if len(argv) > 5 else None
    full = json.loads(pathlib.Path(src).read_text())
    w0, _ = T.window_ns(full)
    a = w0 + int(float(offset_us) * 1000)
    b = a + int(float(length_us) * 1000)
    cut = {}
    for plane in T.device_planes(full):
        cut[plane] = {
            line: [[e[0][:name_chars], e[1], e[2]] for e in events
                   if a <= e[1] and e[1] + e[2] <= b]
            for line, events in full[plane].items()}
    spans = [[name, max(s, a), min(s + d, b) - max(s, a)]
             for name, s, d in T.host_spans(full)
             if name != T.WINDOW_SPAN and s < b and s + d > a]
    cut["/host:CPU"] = {"python": [[T.WINDOW_SPAN, a, b - a]] + spans}
    planes = T.device_planes(cut)
    matching = {}
    paints = []
    for plane in planes:
        paint = np.zeros(b - a, bool)
        for _, s, d in cut[plane].get(T.OPS_LINE, []):
            paint[s - a:s - a + d] = True
        paints.append(paint)
    busy = sum(int(paint.sum()) for paint in paints)
    for pattern in patterns.split(","):
        total = count = 0
        for plane in planes:
            for name, _, d in cut[plane].get(T.OPS_LINE, []):
                if re.search(pattern, name):
                    total, count = total + d, count + 1
        matching[pattern] = [total / len(planes) / 1e9, count / len(planes)]
    # Who holds each nanosecond: paint the longest span first, so that the
    # shortest, the innermost, is what is left on top.
    names = sorted({s[0] for s in spans})
    holder = np.zeros(b - a, np.int16)
    for name, s, d in sorted(spans, key=lambda e: -e[2]):
        holder[s - a:s - a + d] = names.index(name) + 1
    held = np.bincount(holder[~paints[0]], minlength=len(names) + 1)
    idle_by = {"unattributed": int(held[0]), **{
        n: int(held[i + 1]) for i, n in enumerate(names)}}
    out = {"trace": cut,
           "by_hand": {"window_s": (b - a) / 1e9,
                       "busy_s": busy / len(planes) / 1e9,
                       "matching": matching,
                       "idle_by_span_s": {k: v / 1e9 for k, v
                                          in idle_by.items() if v}}}
    dest.parent.mkdir(parents=True, exist_ok=True)
    dest.write_text(json.dumps(out))
    print(json.dumps(out["by_hand"]), dest.stat().st_size, "bytes")


if __name__ == "__main__":
    main(sys.argv[1:])
