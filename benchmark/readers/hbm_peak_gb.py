"""Peak HBM on the fullest chip: bytes resident after the window plus the
temporaries of the largest program the run used, from XLA's
``memory_analysis()`` (``memory_stats()`` does not see them here)."""


def read(facts, args, ctx):
    if "resident_bytes" not in facts or not facts["resident_bytes"]:
        return None
    return (facts["resident_bytes"] + facts.get("step_temp_bytes", 0)) / 1e9
