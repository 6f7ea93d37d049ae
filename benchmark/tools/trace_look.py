"""Look at one profiler trace by hand: every plane, every line, event
counts, the names that take most time and a few events' stats.

    python benchmark/tools/trace_look.py <dir or .xplane.pb> [out.json]
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.harness import trace_reduce  # noqa: E402


def look(path, per_line=25, samples=3):
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = trace_reduce.find_xplane(path)
    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        for line in plane.lines:
            names, sample, n = {}, [], 0
            t_min, t_max = None, None
            for ev in line.events:
                n += 1
                key = ev.name[:120]
                c = names.setdefault(key, [0, 0])
                c[0] += 1
                c[1] += ev.duration_ns
                s = int(ev.start_ns)
                t_min = s if t_min is None else min(t_min, s)
                t_max = max(t_max or 0, s + int(ev.duration_ns))
                if len(sample) < samples:
                    sample.append({"name": ev.name[:200],
                                   "start_ns": s,
                                   "dur_ns": int(ev.duration_ns),
                                   "stats": {k: str(v)[:300]
                                             for k, v in ev.stats}})
            top = sorted(names.items(), key=lambda kv: -kv[1][1])[:per_line]
            out.append({"plane": plane.name, "line": line.name, "events": n,
                        "span_ns": [t_min, t_max],
                        "top": [[k, c, t] for k, (c, t) in top],
                        "samples": sample})
    return out


if __name__ == "__main__":
    result = look(sys.argv[1])
    text = json.dumps(result, indent=1)
    if len(sys.argv) > 2:
        with open(sys.argv[2], "w") as f:
            f.write(text)
    else:
        print(text)
