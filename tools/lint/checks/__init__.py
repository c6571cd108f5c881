"""Checker registry: id -> function(Module) -> [Finding].

Each checker lives in its own module and encodes ONE invariant the
codebase already claims (see tools/lint/__init__ for the table and the
PR that established each bar)."""
from .recompile import check_recompile_hazard
from .host_sync import check_host_sync
from .series import check_series_lifecycle
from .locks import check_lock_discipline
from .gating import check_flag_gating
from .socket_io import check_socket_io
from .eager_dispatch import check_eager_dispatch

CHECKERS = {
    "PT001": check_recompile_hazard,
    "PT002": check_host_sync,
    "PT003": check_series_lifecycle,
    "PT004": check_lock_discipline,
    "PT005": check_flag_gating,
    "PT006": check_socket_io,
    "PT007": check_eager_dispatch,
}

__all__ = ["CHECKERS"]
