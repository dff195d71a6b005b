"""Share of the traced window in which a collective runs and no compute
does, per chip, mean over the chips: the part of the ZeRO-3 gathers /
scatters and the tensor-parallel all-reduces that compute does not hide."""
HEADER = {"layer": "collectives (GSPMD, comm/schedule.py)", "unit": "%",
          "moves": "train_tokens_per_s_per_chip", "jobs": ["train"],
          "source": "device_trace", "better": "lower"}


def read(run):
    t = run["trace"]
    if not t or not t.get("devices") or run["chips"] < 2:
        return None
    w = t["window_s"] * 1e9
    return 100.0 * sum(d["collective_exposed_ns"] for d in t["devices"]) \
        / len(t["devices"]) / w
