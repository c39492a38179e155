"""TPU kernels (Pallas): the fused select+pack of the hot compression path
(`pallas_pack`, SURVEY.md §7 stage 6), the experts' grouped products
(`grouped_matmul`), the gated delta rule's chunks (`delta_rule`) and the
convolution and norms before them in the mixer (`delta_prologue`)."""
