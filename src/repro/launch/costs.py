"""Accessors for jax ``Compiled`` introspection.

Import-side-effect free (unlike ``launch.dryrun``, which force-sets the
virtual device count), so tests and tools can import it after jax init.
"""
from __future__ import annotations

from typing import Any, Dict


def cost_dict(compiled) -> Dict[str, Any]:
    """``compiled.cost_analysis()`` as a plain dict."""
    return dict(compiled.cost_analysis())
