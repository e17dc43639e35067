"""How many ticks or steps the traced stretch holds."""


def count(facts, per: str):
    if per == "step":
        return facts.get("traced_steps")
    view = facts.get("view")
    if view is None:
        return None
    return len(view.host_named(r"^bench/tick$")) or None
