"""Plain references that decide ``correct``.  They import nothing of the
program under test and take nothing it made: weights, data and answers
are rebuilt here from the seed and the configuration."""
