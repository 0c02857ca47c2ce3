"""Shared benchmark utilities."""
from __future__ import annotations


def emit(name: str, derived: str = "") -> None:
    """CSV row: name,derived."""
    print(f"{name},{derived}", flush=True)
