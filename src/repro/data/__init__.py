from repro.data.synthetic import (
    bigram_table,
    classif_batch_fn,
    classif_eval_set,
    lm_batch_fn,
    lm_eval_set,
    lm_teacher,
    sample_lm,
    sparse_bigram_teacher,
)
