"""Deep learning: distributed fine-tuning + embedding.

Parity surface: reference ``deep-learning`` python side
(dl/DeepVisionClassifier.py:7-31, dl/DeepTextClassifier.py:1,
hf/HuggingFaceSentenceEmbedder.py:26-60, dl/LitDeepVisionModel.py:1;
``CausalLM`` is the counterpart of hf/HuggingFaceCausalLM.py).
The Horovod-on-Spark + PyTorch Lightning harness is replaced by a flax
train loop whose step is jit-compiled over a `jax.sharding.Mesh`: batch
sharded on ``dp``, params replicated, gradient psum inserted by XLA
(SURVEY.md §2.8 "DNN DP").
"""

from mmlspark_tpu.dl.estimator import DeepEstimator, DeepModel
from mmlspark_tpu.dl.text import DeepTextClassifier, DeepTextModel
from mmlspark_tpu.dl.vision import DeepVisionClassifier, DeepVisionModel
from mmlspark_tpu.dl.embedder import SentenceEmbedder
from mmlspark_tpu.dl.causal_lm import CausalLM

__all__ = ["DeepEstimator", "DeepModel",
           "DeepVisionClassifier", "DeepVisionModel",
           "DeepTextClassifier", "DeepTextModel",
           "SentenceEmbedder", "CausalLM"]
