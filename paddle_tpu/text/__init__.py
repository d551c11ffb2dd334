"""NLP models + datasets. Parity: python/paddle/text/__init__.py."""
from . import datasets
from .bert import (BertConfig, BertModel, BertForPretraining,
                   BertPretrainingHeads, bert_base, bert_large)
from .ernie import (ErnieModel, ErnieForPretraining, ErnieConfig,
                    ernie_knowledge_mask, ernie_mask_batch)
from .gpt import GPTConfig, GPTModel, gpt_small
from .kimi_linear import (KimiLinearConfig, KimiLinearBlock,
                          KimiLinearForCausalLM)
from .joyai_flash import JoyAIFlashConfig, JoyAIFlashForCausalLM
from .olmo_hybrid import OlmoHybridConfig, OlmoHybridForCausalLM
from .mellum import MellumConfig, MellumForCausalLM
from .laguna import LagunaConfig, LagunaForCausalLM
from .nemotron_h import NemotronHConfig, NemotronHForCausalLM
from .seq2seq import Seq2SeqTransformer
from .word2vec import SkipGram, Word2Vec
from .lm import LSTMLanguageModel
from .._native.tokenizer import Tokenizer
from .layers import (RNNCell, BasicLSTMCell, BasicGRUCell, RNN,
                     BidirectionalRNN, StackedRNNCell, StackedLSTMCell,
                     LSTM, BidirectionalLSTM, StackedGRUCell, GRU,
                     BidirectionalGRU, DynamicDecode, BeamSearchDecoder,
                     Conv1dPoolLayer, CNNEncoder, MultiHeadAttention, FFN,
                     TransformerEncoderLayer, TransformerEncoder,
                     TransformerDecoderLayer, TransformerDecoder,
                     TransformerCell, TransformerBeamSearchDecoder,
                     LinearChainCRF, CRFDecoding, SequenceTagging)

# dataset classes at the paddle.text top level (reference text/__init__.py)
from .datasets import (Conll05st, Imdb, Imikolov, Movielens,  # noqa: F401
                       UCIHousing, WMT14, WMT16)
from .datasets import Sentiment as MovieReviews  # noqa: F401
# (the reference's movie_reviews.py NLTK polarity set; one loader, 1.8
# name Sentiment + 2.0-beta name MovieReviews)
