"""Forward + backward programs: device time a step of the operations
traced under the program's scope ``mx.attention`` in a cell of the
``nemotron_h`` family (grouped-query attention with K and V repeated to
the 32 query heads of 128: the flash pair, one head a grid step). The
reading is ``attention_device_ms``'s own, listed for this family's cells;
the repeat of K and V lies outside the scope. A later ``benchmark`` issue
may fold the three pairs into one."""

from .attention_device_ms import read  # noqa: F401
