"""In-tree Flax model zoo with torchvision registry semantics.

Importing this package populates the registry (the analog of torchvision's
module-dict discovery, imagenet_ddp.py:19-21). ``model_names()`` and
``create_model()`` are the CLI-facing surface.
"""

from dptpu.models import alexnet as _alexnet  # noqa: F401
from dptpu.models import convnext as _convnext  # noqa: F401
from dptpu.models import densenet as _densenet  # noqa: F401
from dptpu.models import efficientnet as _efficientnet  # noqa: F401
from dptpu.models import googlenet as _googlenet  # noqa: F401
from dptpu.models import granite as _granite  # noqa: F401
from dptpu.models import inception as _inception  # noqa: F401
from dptpu.models import joyai as _joyai  # noqa: F401
from dptpu.models import lfm2 as _lfm2  # noqa: F401
from dptpu.models import maxvit as _maxvit  # noqa: F401
from dptpu.models import mnasnet as _mnasnet  # noqa: F401
from dptpu.models import mobilenet as _mobilenet  # noqa: F401
from dptpu.models import mobilenet_v3 as _mobilenet_v3  # noqa: F401
from dptpu.models import regnet as _regnet  # noqa: F401
from dptpu.models import resnet as _resnet  # noqa: F401
from dptpu.models import shufflenet as _shufflenet  # noqa: F401
from dptpu.models import squeezenet as _squeezenet  # noqa: F401
from dptpu.models import swin as _swin  # noqa: F401
from dptpu.models import trinity as _trinity  # noqa: F401
from dptpu.models import vgg as _vgg  # noqa: F401
from dptpu.models import vit as _vit  # noqa: F401
from dptpu.models.registry import (
    create_model,
    model_names,
    model_task,
    register_model,
)

__all__ = ["create_model", "model_names", "model_task", "register_model"]
