"""Ratio of two counters' growth over the window, each summed over a set of
the herd's roles, from their own /metrics: ``{"numerator": {"metric",
"labels", "roles"}, "denominator": {...}, "scale"}``. ``roles`` is
``"agents"`` (``agent``, ``agent-1``, ...) or ``"serving"`` (the origin and
the agents), of the roles the run scraped; a rollout scrapes every role.
The ratio is multiplied by ``scale``. A numerator the program does not
export reads 0; nothing in the denominator, nothing returned."""

from readers import prom_delta


def roles_of(ctx, which: str) -> list[str]:
    agents = [r for r in ctx["prom"] if r == "agent" or r.startswith("agent-")]
    if which == "agents":
        return agents
    if which == "serving":
        return agents + [r for r in ctx["prom"] if r == "origin"]
    raise ValueError(f"no set of roles {which!r}")


def summed(ctx, side) -> float:
    labels = side.get("labels", {})
    return sum(prom_delta(ctx, role, side["metric"], labels)
               for role in roles_of(ctx, side["roles"]))


def read(ctx, params):
    den = summed(ctx, params["denominator"])
    if not den:
        return None
    return params["scale"] * summed(ctx, params["numerator"]) / den
