"""Forward + backward programs: device time a step of the operations
traced under the program's scope ``mx.attention`` in a cell of the
``deepseek_v3`` family (latent attention: the flash forward kernel on
values padded to the query's width, the recomputing backward). The
reading is ``attention_device_ms``'s own, listed for this family's cells;
a later ``benchmark`` issue may fold the two into one."""

from .attention_device_ms import read  # noqa: F401
