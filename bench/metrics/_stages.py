"""Seconds the program's `core.timing` records put in each stage over the
window (traced runs, where the records are on)."""


def seconds(run, *stages) -> float | None:
    if run.timing is None:
        return None
    return sum(e["first_s"] + e["steady_s"]
               for (stage, _key), e in run.timing.items() if stage in stages)


def sessions_trained(run) -> int:
    return sum(key[0] * e["calls"] for (stage, key), e in run.timing.items()
               if stage == "train_fused")


TIMED = ("train_fused", "select_stacked", "select_solo", "encode_stacked",
         "encode_solo")
