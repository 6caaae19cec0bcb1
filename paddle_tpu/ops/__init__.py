"""Op lowering rules. Importing this package registers all ops."""
from . import basic      # noqa: F401
from . import nn_ops     # noqa: F401
from . import optimizer_ops  # noqa: F401
from . import sequence_ops   # noqa: F401
from . import control_ops    # noqa: F401
from . import crf_ops        # noqa: F401
from . import ctc_ops        # noqa: F401
from . import detection_ops  # noqa: F401
from . import parallel_ops   # noqa: F401
from . import tail_ops       # noqa: F401
from . import volumetric_ops  # noqa: F401
from . import guard_ops      # noqa: F401
from . import quant_ops      # noqa: F401
from . import linear_attention_ops  # noqa: F401
from . import hyper_connection_ops  # noqa: F401
