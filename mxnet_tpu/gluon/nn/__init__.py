"""``gluon.nn`` — neural-network layers."""

from .activations import *  # noqa: F401,F403
from .basic_layers import *  # noqa: F401,F403
from .conv_layers import *  # noqa: F401,F403
from .sparse_layers import *  # noqa: F401,F403
from .ssm_layers import *  # noqa: F401,F403
from .kda_layers import *  # noqa: F401,F403
