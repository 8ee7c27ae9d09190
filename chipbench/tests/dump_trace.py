"""Look at a trace by hand: host lines, annotation events, device module runs around them."""
import sys, glob, json
from jax.profiler import ProfileData
path = sys.argv[1]
if not path.endswith(".pb"):
    path = sorted(glob.glob(path + "/**/*.xplane.pb", recursive=True))[-1]
n = int(sys.argv[2]) if len(sys.argv) > 2 else 60
data = ProfileData.from_file(path)
for plane in data.planes:
    print("PLANE", plane.name)
    for i, line in enumerate(plane.lines):
        evs = list(line.events)
        ann = [e for e in evs if e.name.startswith(("engine.", "train."))]
        print(f"  LINE {i} {line.name!r}: {len(evs)} events, {len(ann)} annotations")
        if plane.name.startswith("/device:") and line.name == "XLA Modules":
            for e in evs[:n]:
                print(f"      {e.start_ns/1e6:12.3f} ms +{e.duration_ns/1e6:9.3f}  {e.name[:60]}")
        for e in ann[:n]:
            print(f"      {e.start_ns/1e6:12.3f} ms +{e.duration_ns/1e6:9.3f}  {e.name} {dict(e.stats)}")
