from benchmark_checks import names_of

globals().update(names_of("test_minicpm_sala"))
