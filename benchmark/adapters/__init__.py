"""One module per model family: the only files of the benchmark that
import the program. Each hands the runners the system under test built
from a configuration file, and names the family's plain reference."""
