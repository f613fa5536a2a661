"""Utility subpackage: serialization, config/env flags, misc helpers."""
from . import serialization  # noqa: F401
from .config import env_bool, env_int, env_str  # noqa: F401
