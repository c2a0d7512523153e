"""Model zoo (parity: GluonCV/GluonNLP model zoos reached from
`python/mxnet/gluon/model_zoo/` — SURVEY.md §2.2; BERT/Transformer come
from the external GluonNLP scripts the baselines cite, BASELINE.md)."""

from . import lenet
from .lenet import LeNet
from . import bert
from .bert import (BERTModel, BERTForPretraining, BERTClassifier,
                   bert_base, bert_large, bert_tiny)

__all__ = ["LeNet", "BERTModel", "BERTForPretraining", "BERTClassifier",
           "bert_base", "bert_large", "bert_tiny"]


def __getattr__(name):
    if name in ("resnet", "transformer", "ssd", "gpt", "faster_rcnn",
                "granite_hybrid"):
        import importlib
        mod = importlib.import_module("." + name, __name__)
        globals()[name] = mod
        return mod
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
