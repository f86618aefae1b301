"""Utilities: timers/profiling (stats), flag/config system (flags), numeric
hardening (debug) — the paddle/utils tier."""

from . import debug, flags, gradcheck, interop, stats
from .flags import TrainerFlags, parse_flags
from .gradcheck import check_gradients
from .stats import BarrierStat, StatSet, global_stats, timer
