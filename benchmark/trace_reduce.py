"""From a JAX profiler trace (.xplane.pb) to numbers: device busy and idle
time, the durations of each jitted module and the kernels each execution
called, device time per kernel, the device operations that took most time,
the program's host spans, and who on the host owned the longest idle gaps.
Read with nothing but jax (jax.profiler.ProfileData). Checked on the small
recorded trace in benchmark/tests/data/.

What a TPU trace holds (one plane per chip, `/device:TPU:<n>`):
  line "XLA Modules"  one event per execution of a jitted program
  line "XLA Ops"      one event per HLO operation (fusions, custom calls)
Host threads are lines of the plane `/host:CPU`; the program's
TraceAnnotation spans (engine.decode, engine.mixed, engine.prefill; on when
DYN_ENABLE_JAX_TRACE is set) are events there, on the same clock.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from statistics import median

# a Pallas kernel reaches the device as a Mosaic custom call; on the chip an
# event of "XLA Ops" is named by its HLO text, `%<id> = <shape> <opcode>(...)`
KERNEL_PATTERN = r" custom-call\("
HOST_SPAN_PATTERN = r"^engine\."


def _base(name: str) -> str:
    """`jit__decode_loop(1234)` -> `jit__decode_loop`."""
    return re.sub(r"\(\d+\)$", "", name)


def op_id(name: str) -> str:
    """`%fusion.12 = bf16[..] fusion(...)` -> `fusion.12`; other names stay."""
    m = re.match(r"%?([\w\-.]+) = ", name)
    return m.group(1) if m else name[:80]


def op_base(name: str) -> str:
    return re.sub(r"\.\d+$", "", op_id(name))


def union_length(intervals: list) -> float:
    """Total length covered by [start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps_of(intervals: list, lo: float, hi: float) -> list:
    """The [start, end) gaps that the union of `intervals` leaves in [lo, hi)."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(a, b) for a, b in out if b > a]


def self_times(ops: list) -> list:
    """`ops` sorted by (start, -duration): each event's duration minus its
    direct children's (a `while` event spans the operations of its body)."""
    out = [d for _, _, d in ops]
    stack: list = []
    for i, (_, s, d) in enumerate(ops):
        while stack and ops[stack[-1]][1] + ops[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            out[stack[-1]] -= d
        stack.append(i)
    return out


def reduce_planes(planes: list, n_devices: int = 0,
                  kernel_pattern: str = KERNEL_PATTERN) -> dict:
    """`planes`: [{"name", "lines": [{"name", "events": [(name, start_ns,
    dur_ns)]}]}] — the shape read_planes builds from ProfileData, and the
    shape the tests hand-make. A module is labelled `<program>[<the kernel
    it calls most>]` (`jit_decode_loop[decode_paged_attention]`; a program
    whose jitted partial lost its name traces as `jit__unknown`), and each
    execution keeps the calls of every kernel by name. `kernels` is device
    time per kernel over all custom calls (seconds averaged over the chips,
    like `op_time`; calls and durations over all of them), `host_spans` the
    program's spans by name."""
    kre, hre = re.compile(kernel_pattern), re.compile(HOST_SPAN_PATTERN)
    dev = [p for p in planes if p["name"].startswith("/device:TPU:")]
    dev.sort(key=lambda p: int(p["name"].rsplit(":", 1)[1]))
    if n_devices:
        dev = dev[:n_devices]
    host_spans = sorted((s, s + d, name) for p in planes if p["name"].startswith("/host:")
                        for ln in p["lines"] for name, s, d in ln["events"] if hre.search(name))
    host_starts = [h[0] for h in host_spans]

    def owner(t: float) -> str:
        i = bisect.bisect_right(host_starts, t)
        for s, e, name in reversed(host_spans[max(0, i - 8): i]):
            if s <= t < e:
                return name
        return "unattributed"

    out = {"planes": [p["name"] for p in planes], "n_devices": len(dev), "per_device": {},
           "modules": {}, "window_s": 0.0, "busy_s": 0.0, "kernel_s": 0.0,
           "op_time": {}, "gap_owner": {}, "kernels": {}, "host_spans": {}}
    for s0, e0, name in host_spans:
        h = out["host_spans"].setdefault(name, {"n": 0, "total_s": 0.0})
        h["n"], h["total_s"] = h["n"] + 1, h["total_s"] + (e0 - s0) / 1e9
    if not dev:
        return out
    evs = [(s, s + d) for p in dev for ln in p["lines"] for _, s, d in ln["events"]]
    lo, hi = min(a for a, _ in evs), max(b for _, b in evs)
    out["window_s"] = (hi - lo) / 1e9
    op_time, gap_owner = defaultdict(float), defaultdict(float)
    modules = defaultdict(lambda: {"durations_ms": [], "kernel_calls": [], "kernels": []})
    kernels = defaultdict(lambda: {"total_s": 0.0, "durations_us": []})
    for p in dev:
        lines = {ln["name"]: ln["events"] for ln in p["lines"]}
        ops = sorted(lines.get("XLA Ops", []), key=lambda e: (e[1], -e[2]))
        selfs = self_times(ops)
        iv = [(s, s + d) for _, s, d in ops]
        b = union_length(iv)
        kern = [(s, op_base(n)) for n, s, d in ops if kre.search(n)]
        kstarts = [k[0] for k in kern]
        mods = sorted(lines.get("XLA Modules", []), key=lambda e: e[1])
        labels = []
        for n, s, d in mods:
            calls = defaultdict(int)
            for _, kb in kern[bisect.bisect_left(kstarts, s): bisect.bisect_left(kstarts, s + d)]:
                calls[kb] += 1
            top = max(calls.items(), key=lambda kv: kv[1]) if calls else ("-", 0)
            label = f"{_base(n)}[{top[0]}]"
            labels.append(label)
            modules[label]["durations_ms"].append(d / 1e6)
            modules[label]["kernel_calls"].append(top[1])
            modules[label]["kernels"].append(dict(calls))
        mstarts = [m[1] for m in mods]
        for (n, s, d), st in zip(ops, selfs):
            i = bisect.bisect_right(mstarts, s) - 1
            inside = i >= 0 and s < mods[i][1] + mods[i][2]
            op_time[(labels[i] if inside else "-") + "/" + op_id(n)] += st / 1e9 / len(dev)
            if kre.search(n):
                out["kernel_s"] += d / 1e9 / len(dev)
                kernels[op_base(n)]["total_s"] += d / 1e9 / len(dev)
                kernels[op_base(n)]["durations_us"].append(d / 1e3)
        for a, e in gaps_of(iv, lo, hi):
            gap_owner[owner((a + e) / 2)] += (e - a) / 1e9 / len(dev)
        out["busy_s"] += b / 1e9 / len(dev)
        out["per_device"][p["name"]] = {"busy_s": b / 1e9, "n_ops": len(ops),
                                        "lines": sorted(lines)}
    out["modules"], out["kernels"] = dict(modules), dict(kernels)
    out["op_time"], out["gap_owner"] = dict(op_time), dict(gap_owner)
    return out


def merge(parts: list) -> dict:
    """Several captures of one run as one: times add up, lists join."""
    out = {"planes": parts[0]["planes"], "n_devices": parts[0]["n_devices"],
           "captures": len(parts), "per_capture": [
               {"window_s": p["window_s"], "busy_s": p["busy_s"]} for p in parts],
           "window_s": sum(p["window_s"] for p in parts),
           "busy_s": sum(p["busy_s"] for p in parts),
           "kernel_s": sum(p["kernel_s"] for p in parts), "modules": {}}
    op_time, gap_owner = defaultdict(float), defaultdict(float)
    mods = defaultdict(lambda: {"durations_ms": [], "kernel_calls": [], "kernels": []})
    kernels = defaultdict(lambda: {"total_s": 0.0, "durations_us": []})
    spans = defaultdict(lambda: {"n": 0, "total_s": 0.0})
    for p in parts:
        for k, v in p["op_time"].items():
            op_time[k] += v
        for k, v in p["gap_owner"].items():
            gap_owner[k] += v
        for k, m in p["modules"].items():
            mods[k]["durations_ms"] += m["durations_ms"]
            mods[k]["kernel_calls"] += m["kernel_calls"]
            mods[k]["kernels"] += m["kernels"]
        for k, v in p["kernels"].items():
            kernels[k]["total_s"] += v["total_s"]
            kernels[k]["durations_us"] += v["durations_us"]
        for k, v in p["host_spans"].items():
            spans[k]["n"], spans[k]["total_s"] = spans[k]["n"] + v["n"], spans[k]["total_s"] + v["total_s"]
    for name, m in mods.items():
        out["modules"][name] = {
            "n": len(m["durations_ms"]), "total_s": sum(m["durations_ms"]) / 1e3,
            "median_ms": median(m["durations_ms"]),
            "durations_ms": m["durations_ms"][:4000], "kernel_calls": m["kernel_calls"][:4000],
            "kernels": m["kernels"][:4000]}
    out["kernels"] = {k: {"calls": len(v["durations_us"]), "total_s": v["total_s"],
                          "median_us": median(v["durations_us"])} for k, v in kernels.items()}
    out["host_spans"] = dict(spans)
    ranked = sorted(op_time.items(), key=lambda kv: -kv[1])
    out["device_ops"] = [[k, v] for k, v in ranked[:10]]
    out["idle_gaps"] = [[k, v] for k, v in
                        sorted(gap_owner.items(), key=lambda kv: -kv[1])[:10]]
    return out


def read_planes(path: str) -> list:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    planes = []
    for p in pd.planes:
        name = p.name
        keep = name.startswith("/device:TPU:") or name.startswith("/host:")
        lines = []
        if keep:
            for ln in p.lines:
                if name.startswith("/device:") and ln.name not in ("XLA Ops", "XLA Modules"):
                    lines.append({"name": ln.name, "events": []})
                    continue
                evs = [(e.name, float(e.start_ns), float(e.duration_ns)) for e in ln.events]
                if name.startswith("/host:"):
                    evs = [e for e in evs if re.search(HOST_SPAN_PATTERN, e[0])]
                lines.append({"name": ln.name, "events": evs})
        planes.append({"name": name, "lines": lines})
    return planes


def reduce_file(path: str, n_devices: int = 0, **kw) -> dict:
    return merge([reduce_planes(read_planes(path), n_devices, **kw)])


def reduce_dir(trace_dir: str, n_devices: int = 0, **kw) -> dict:
    """Every capture under `trace_dir` (one .xplane.pb each), merged."""
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    out = merge([reduce_planes(read_planes(f), n_devices, **kw) for f in files])
    out["file_bytes"] = sum(os.path.getsize(f) for f in files)
    return out


if __name__ == "__main__":
    import json
    import sys

    r = reduce_file(sys.argv[1])
    for m in r["modules"].values():
        m.pop("durations_ms"), m.pop("kernel_calls"), m.pop("kernels")
    print(json.dumps(r, indent=1))
