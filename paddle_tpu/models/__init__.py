"""Model zoo — the reference's flagship configs (BASELINE.json).

  resnet       ResNet-18/50/101 (ImageNet/CIFAR)   ref: dist_se_resnext.py, book
  bert         BERT-base/large pretraining          ref: PaddleNLP Fluid bert
  transformer  WMT en-de base/big NMT               ref: dist_transformer.py
  ctr          DeepFM / Wide&Deep CTR               ref: dist_ctr.py
  word2vec     N-gram LM + skip-gram NCE            ref: book test_word2vec.py
  mnist        smoke-test models                    ref: book recognize_digits
"""

from paddle_tpu.models import (bert, ctr, ernie, gpt, mnist, recommender, resnet, sentiment, seq2seq,
                               tagging, transformer, vision_cls, word2vec)
from paddle_tpu.models.resnet import ResNet, resnet18, resnet50
from paddle_tpu.models.seq2seq import AttentionSeq2Seq, Seq2SeqConfig, nmt_loss
from paddle_tpu.models.tagging import BiLstmCrfTagger, TaggerConfig
from paddle_tpu.models.recommender import RecommenderNet, RecConfig, rating_loss
from paddle_tpu.models.vision_cls import VGG, SEResNeXt, se_resnext50, vgg16
from paddle_tpu.models.bert import BertConfig, BertEncoder, BertForPretraining
from paddle_tpu.models.transformer import Transformer, TransformerConfig
from paddle_tpu.models.ctr import CTRConfig, DeepFM, WideAndDeep
from paddle_tpu.models.gpt import GPT, GPTConfig, GPTDecoder
from paddle_tpu.models.word2vec import SkipGramNCE, Word2Vec
from paddle_tpu.models.mnist import (MLP, ConvNet, LinearRegression,
                                     SoftmaxRegression)
