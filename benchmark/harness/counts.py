"""Operations and bytes a cell's work needs, from its shapes alone."""

from __future__ import annotations


def sar_scores(users: int, items: int, value_bytes: int = 4) -> dict:
    """What scoring every user against every item needs: one multiply and
    one add per (user, item, item) triple, and the affinity, the seen mask
    and the similarity read once."""
    return {"ops": 2.0 * users * items * items,
            "bytes": float(users) * items * (value_bytes + 1)
                     + float(items) * items * value_bytes}


def least_seconds(need: dict, peaks: dict) -> "tuple[float, str]":
    by_ops = need["ops"] / peaks["flops_per_s"]
    by_bytes = need["bytes"] / peaks["bytes_per_s"]
    return (by_ops, "ops") if by_ops >= by_bytes else (by_bytes, "bytes")
