"""The one place the serving tests build their engine.

``paged_engine`` takes the capacity a test wants the way the dense-slab
engine took it (``max_batch`` rows of ``max_len`` positions) and gives
the paged engine exactly that much: ``max_pages = ceil(max_len /
page_size)`` pages a row and ``num_pages = max_batch * max_pages`` in the
pool, so no test meets pool pressure it did not ask for. A test that
wants pressure, or a file with a geometry of its own, passes
``num_pages`` / ``max_pages`` itself (``functools.partial`` for a file's
defaults). ``BareEngine`` is a paged engine seen without its pages, for
the legs that hold ``Server``'s guards to a caller-supplied engine.
"""
from paddle_tpu.inference.generation import PagedContinuousBatchingEngine


def paged_engine(model, max_batch=4, max_len=32, page_size=8,
                 **engine_kwargs):
    max_pages = engine_kwargs.pop("max_pages", -(-max_len // page_size))
    num_pages = engine_kwargs.pop("num_pages", max_batch * max_pages)
    return PagedContinuousBatchingEngine(
        model, max_batch=max_batch, num_pages=num_pages,
        page_size=page_size, max_pages=max_pages, **engine_kwargs)


class BareEngine:
    """A paged engine that hides ``alloc``, ``admission_mode`` and
    ``set_kv_dtype``: what ``Server`` sees of an engine without pages
    (its pressure, admission-mode and kv-dtype guards check a
    caller-supplied object by those attributes). Everything else passes
    through."""

    _HIDDEN = frozenset({"alloc", "admission_mode", "set_kv_dtype"})

    def __init__(self, engine):
        object.__setattr__(self, "_engine", engine)

    def __getattr__(self, name):
        if name in self._HIDDEN:
            raise AttributeError(name)
        return getattr(self._engine, name)

    def __setattr__(self, name, value):
        setattr(self._engine, name, value)
