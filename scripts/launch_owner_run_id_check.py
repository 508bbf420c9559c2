"""Check `chipbench/readers/launch_owner.py`'s order match against the
runtime's own link from a host call to its launch (PR 35).

The reader matches the host's `PjitFunction(<name>)` calls to chip 0's
launches by name and order, because `chipbench/xplane.py` keeps no event's
arguments.  The trace itself holds the link: the call (or an event its
producer / consumer flow ids `_p` / `_c` lead to) encloses a
`DoEnqueueProgram` with a `run_id`, and the `XLA Modules` event of chip 0
with that `run_id` is the launch.  This reads both with
`jax.profiler.ProfileData` and counts the launches on which they disagree.

    JAX_PLATFORMS=cpu python scripts/launch_owner_run_id_check.py \
        <trace-dir | file.xplane.pb[.gz]>

prints one JSON object: `matched_agree` / `matched_disagree` (the reader's
call is / is not the runtime's), `matched_truth_unknown` (the trace links
the launch to no single call), `no_call_found` (the reader said `unowned`:
numbers differ, or out of call order) and the pairs of names under which
one executable ran.  `chipbench/run.py --trace 1 --trace-dir <dir>` leaves
a trace to read.
"""
import bisect
import collections
import glob
import gzip
import json
import os
import sys

from jax.profiler import ProfileData

BENCH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                     "chipbench")
sys.path[:0] = [BENCH, os.path.join(BENCH, "readers")]
import launch_owner  # noqa: E402
import xplane  # noqa: E402


def load(path):
    """-> the host's threads as sorted (start, end, name, stats), and chip
    0's launches by `run_id`."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        data = ProfileData.from_serialized_xspace(f.read())
    threads, launch_of_run = [], {}
    for plane in data.planes:
        if plane.name == "/device:TPU:0":
            for line in plane.lines:
                if line.name == "XLA Modules":
                    for e in line.events:
                        launch_of_run[dict(e.stats)["run_id"]] = (
                            int(e.start_ns), int(e.start_ns + e.duration_ns),
                            e.name)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                threads.append(sorted(
                    ((int(e.start_ns), int(e.start_ns + e.duration_ns),
                      e.name, dict(e.stats)) for e in line.events),
                    key=lambda ev: (ev[0], -ev[1])))
    return threads, launch_of_run


def run_ids_of_calls(threads, launch_of_run):
    """-> {launch start on chip 0: (its call's start, the call's program)}
    for every host call that the trace links to exactly one launch."""
    starts = [[ev[0] for ev in th] for th in threads]
    consumers = {(ev[3].get("_ct"), ev[3]["_c"]): (ti, ev)
                 for ti, th in enumerate(threads) for ev in th
                 if "_c" in ev[3]}

    def on_chip_0(stats):
        return "run_id" in stats and stats.get("device_ordinal", 0) == 0

    def run_ids(ti, s, e, depth=0):
        th, out = threads[ti], []
        i = bisect.bisect_left(starts[ti], s)
        while i < len(th) and th[i][0] < e:
            ev = th[i]
            i += 1
            if ev[1] > e:
                continue
            if on_chip_0(ev[3]):
                out.append(ev[3]["run_id"])
            hit = consumers.get((ev[3].get("_pt"), ev[3].get("_p")))
            if hit is not None and hit[1] is not ev and depth < 8:
                cti, cev = hit
                if on_chip_0(cev[3]):
                    out.append(cev[3]["run_id"])
                out += run_ids(cti, cev[0], cev[1], depth + 1)
        return out

    truth = {}
    plain = [[ev[:3] for ev in th] for th in threads]
    for cs, ce, program, ti in launch_owner.host_calls(plain):
        ids = set(run_ids(ti, cs, ce)) & set(launch_of_run)
        if len(ids) == 1:
            truth[launch_of_run[ids.pop()][0]] = (cs, program)
    return truth


def check(path):
    if os.path.isdir(path):
        [path] = glob.glob(os.path.join(path, "plugins", "profile", "*",
                                        "*.xplane.pb"))
    threads, launch_of_run = load(path)
    truth = run_ids_of_calls(threads, launch_of_run)
    owned = launch_owner.owned_launches(xplane.load(path))
    out = {"launches_chip0": len(launch_of_run),
           "calls_with_one_run_id": len(truth)}
    if owned is None:
        return out
    counts, renamed = collections.Counter(), collections.Counter()
    for ln in owned:
        t = truth.get(ln.start)
        if t is not None and t[1] != ln.program:
            renamed[f"{t[1]} -> {ln.program}"] += 1
        counts["no_call_found" if ln.call_start is None
               else "matched_truth_unknown" if t is None
               else "matched_agree" if t[0] == ln.call_start
               else "matched_disagree"] += 1
    out.update({k: counts[k] for k in (
        "matched_agree", "matched_disagree", "matched_truth_unknown",
        "no_call_found")})
    out["disagree_share_of_known"] = 100.0 * counts["matched_disagree"] / max(
        counts["matched_agree"] + counts["matched_disagree"], 1)
    out["call_name_differs_from_launch_name"] = dict(renamed.most_common(10))
    return out


if __name__ == "__main__":
    print(json.dumps(check(sys.argv[1])))
