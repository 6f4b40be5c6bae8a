from .mesh import (batch_sharded, make_mesh, pad_to_multiple,  # noqa: F401
                   put_batch, put_replicated, replicated)
from . import precision  # noqa: F401
