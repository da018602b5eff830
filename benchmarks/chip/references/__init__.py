"""Plain references, written from the published descriptions. They import
nothing of the program and take nothing it made: weights and inputs come
from the seed through the functions here."""
