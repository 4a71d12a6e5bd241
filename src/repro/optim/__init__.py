from repro.optim.base import SGD, Adam, apply_updates  # noqa: F401
from repro.optim.distributed import DashaTrainConfig  # noqa: F401
